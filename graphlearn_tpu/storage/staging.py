"""Chunk-boundary staging pipeline: disk -> pinned host ring -> warm tier.

The reference hides feature-fetch latency behind CUDA streams and UVA
zero-copy (PAPER.md, unified_tensor.cu); PyTorch-Direct (arxiv
2101.07956) and GPU-initiated storage access (arxiv 2306.16384) are the
GPU-world exemplars. The TPU analog is *double-buffered host staging
fused to the scanned epoch's chunk cadence*: the whole epoch's miss set
is computable at the prologue (storage/planner.py), so while chunk ``c``
trains on device, a single bounded worker thread gathers chunk
``c+1``'s warm/disk rows into a host ring slab (pow2-padded — the
chunk program's staging shapes form a closed set) and hands it to the
dispatch thread at the chunk boundary.

Failure semantics (docs/failure_model.md): a failed or slow staging
worker NEVER yields a wrong batch — :meth:`ChunkStager.take` falls back
to a synchronous on-demand gather of the SAME planned row set (counted
by ``storage.prefetch_miss``), so the degraded epoch is bit-identical
to the healthy one, just slower. Fault sites ``storage.stage`` (the
worker's gather) and ``storage.promote`` (handing the slab to the
ring) are registered in utils/faults.py for the chaos suite.

Observability: ``storage.staged_rows`` / ``storage.staged_bytes``
counters, ``storage.stage_ms`` / ``storage.promote_ms`` histograms, a
``storage.ring_rows`` gauge, and one ``storage.stage`` span per staged
chunk (docs/observability.md).
"""
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .. import metrics
from ..metrics import spans
from ..utils.faults import fault_point

INT32_MAX = np.iinfo(np.int32).max


def pow2_slab_cap(n: int) -> int:
  """Padded slab capacity: next power of two, floor 1 — the staging
  analog of UnifiedTensor's pow2 cold caps (one executable per shape)."""
  if n <= 1:
    return 1
  return 1 << int(n - 1).bit_length()


def pad_slab(row_ids: np.ndarray, rows: np.ndarray):
  """(ids [cap], rows [cap, F]) pow2-padded; pad id slots carry
  INT32_MAX so an in-program searchsorted can never match them."""
  n = int(row_ids.shape[0])
  cap = pow2_slab_cap(n)
  ids = np.full((cap,), INT32_MAX, np.int32)
  ids[:n] = row_ids
  out = np.zeros((cap,) + rows.shape[1:], rows.dtype)
  out[:n] = rows
  return ids, out


class _Slab:
  __slots__ = ('ids', 'rows', 'ready', 'error', 'staged_async', 't_done')

  def __init__(self):
    self.ids = None
    self.rows = None
    self.ready = threading.Event()
    self.error: Optional[BaseException] = None
    self.staged_async = False
    self.t_done: Optional[float] = None


class ChunkStager:
  """One background worker staging planned chunk slabs ahead of the
  dispatch loop.

  Args:
    store: the TieredFeature whose warm/disk tiers to read
      (``store.stage_gather(abs_rows)``).
    max_ahead: outstanding staged chunks (2 = classic double buffer:
      slab c+1 fills while chunk c trains).
    timeout_s: how long :meth:`take` waits for the worker before
      degrading to a synchronous gather.
  """

  def __init__(self, store, max_ahead: int = 2, timeout_s: float = 30.0):
    if max_ahead < 1:
      raise ValueError('max_ahead must be >= 1')
    self.store = store
    self.max_ahead = int(max_ahead)
    self.timeout_s = float(timeout_s)
    self._lock = threading.Lock()
    # ring state shared between the dispatch thread (begin_epoch/take/
    # ack) and the stager worker (_loop) — every access holds _lock
    # graftlint: shared[_lock]
    self._plan: List[np.ndarray] = []
    # graftlint: shared[_lock]
    self._slabs: Dict[int, _Slab] = {}
    self._q: 'queue.Queue' = queue.Queue()
    self._worker: Optional[threading.Thread] = None
    self._stop = False
    # graftlint: shared[_lock]
    self._next_submit = 0
    self.degraded = False   # a worker gather failed this epoch
    # the largest slab capacity this stager has made, kept across
    # epochs: a slab is never padded to less, so the chunk program's
    # slab shape only grows and a steady job settles on ONE executable
    # (a chunk whose miss count falls under a power of two that its
    # neighbours pass would otherwise compile a second one, mid-run)
    # graftlint: shared[_lock]
    self._cap_floor = 1
    # perf_counter marks per chunk, kept for the whole epoch — the
    # chunk-boundary-overlap contract ("stage of c+1 completes before
    # chunk c's ack") is asserted from these
    self.stage_done_t: Dict[int, float] = {}
    self.ack_t: Dict[int, float] = {}

  # ------------------------------------------------------------ lifecycle

  def begin_epoch(self, chunk_rows: List[np.ndarray],
                  start_chunk: int = 0):
    """Install this epoch's plan (per-chunk sorted absolute storage
    rows beyond the hot tier) and prime the first ``max_ahead`` slabs.
    Any previous epoch's outstanding slabs are dropped. A mid-epoch
    RESUME (recovery/checkpoint.py) passes ``start_chunk``: the plan
    keeps its absolute chunk indexing and staging starts at that
    chunk — earlier chunks were consumed before the crash and are
    never staged again."""
    if not 0 <= start_chunk <= len(chunk_rows):
      raise ValueError(f'start_chunk={start_chunk} outside the '
                       f'{len(chunk_rows)}-chunk plan')
    with self._lock:
      self._plan = list(chunk_rows)
      self._slabs = {}
      self._next_submit = int(start_chunk)
      self.degraded = False
      self.stage_done_t = {}
      self.ack_t = {}
    self._ensure_worker()
    # sized from the argument, not self._plan — the worker owns the
    # ring state once _ensure_worker starts it, so reads go through
    # the lock (or, like here, never touch the shared field at all)
    for _ in range(min(self.max_ahead,
                       len(chunk_rows) - int(start_chunk))):
      self._submit_next()

  def watermarks(self) -> Dict[str, int]:
    """Ring position snapshot for checkpoint metadata: the next chunk
    the worker will be asked to stage and the slabs currently held."""
    with self._lock:
      return dict(next_submit=int(self._next_submit),
                  held=len(self._slabs), planned=len(self._plan))

  def close(self):
    self._stop = True
    self._q.put(None)
    w = self._worker
    if w is not None:
      w.join(timeout=5.0)
    self._worker = None
    self._stop = False
    # drain whatever the dead worker left behind (queued chunk ids, the
    # None sentinel itself when the worker exited on a chunk id + _stop
    # instead): a stale None would kill the NEXT epoch's fresh worker on
    # its first pop, silently degrading every take() to the timeout path
    try:
      while True:
        self._q.get_nowait()
    except queue.Empty:
      pass

  def _ensure_worker(self):
    if self._worker is not None and self._worker.is_alive():
      return
    self._worker = threading.Thread(target=self._loop, daemon=True,
                                    name='glt-storage-stager')
    self._worker.start()

  def _submit_next(self):
    with self._lock:
      c = self._next_submit
      if c >= len(self._plan):
        return
      self._next_submit = c + 1
      self._slabs[c] = _Slab()
    self._q.put(c)

  # --------------------------------------------------------------- worker

  def _loop(self):
    while True:
      c = self._q.get()
      if c is None or self._stop:
        return
      with self._lock:
        slab = self._slabs.get(c)
        planned = c < len(self._plan)
      if slab is None or not planned:
        continue   # epoch moved on under us
      try:
        with spans.span('storage.stage', chunk=int(c)) as tok:
          t0 = time.perf_counter()
          # worker-only fault seam: armed faults fire HERE, never in
          # take()'s synchronous fallback — the degraded path must be
          # able to gather the same planned rows cleanly
          self._stage_fault()
          rows_abs = self._planned_rows(c)
          tok.attrs['rows'] = int(rows_abs.shape[0])
          ids, rows = self._gather(rows_abs)
          metrics.observe('storage.stage_ms',
                          (time.perf_counter() - t0) * 1e3)
          t1 = time.perf_counter()
          fault_point('storage.promote')
          slab.ids, slab.rows = ids, rows
          slab.staged_async = True
          metrics.inc('storage.staged_rows', int(rows_abs.shape[0]))
          metrics.inc('storage.staged_bytes', int(rows.nbytes))
          metrics.observe('storage.promote_ms',
                          (time.perf_counter() - t1) * 1e3)
          metrics.set_gauge('storage.ring_rows', self._ring_rows())
      except BaseException as e:   # a chaos 'raise' must not kill later chunks
        slab.error = e
        self.degraded = True
      finally:
        slab.t_done = time.perf_counter()
        with self._lock:
          self.stage_done_t[c] = slab.t_done
        slab.ready.set()

  def _stage_fault(self):
    """The worker-thread fault site (chaos suite). Subclasses override
    with their own registered literal name (the dist staging pipeline's
    ``storage.dist_stage``, storage/dist_scan.py)."""
    fault_point('storage.stage')

  def _planned_rows(self, c: int) -> np.ndarray:
    """Chunk ``c``'s sorted miss set. A plan entry may be a zero-argument
    callable (``planner.EpochPlan.thunks``, which resolves a chunk once
    and keeps it: the chunk's rows are still a device block, fetched and
    deduplicated HERE, on whichever thread asks first — the worker,
    beside the gather, so the dispatch thread never sorts while the
    device waits)."""
    with self._lock:
      rows = self._plan[c]
    return rows() if callable(rows) else rows

  def _gather(self, rows_abs: np.ndarray):
    """``pad_slab``'s slab, its rows gathered straight into the padded
    buffer (one copy of a chunk's rows, and only the pad tail zeroed)."""
    n = int(rows_abs.shape[0])
    with self._lock:
      cap = self._cap_floor = max(self._cap_floor, pow2_slab_cap(n))
    ids = np.full((cap,), INT32_MAX, np.int32)
    ids[:n] = rows_abs
    f, dt = self.store.shape[1], self.store._np_dtype
    slab = np.empty((cap, f), dt)
    self.store.stage_gather(rows_abs, out=slab[:n])
    slab[n:] = 0
    return ids, slab

  def _ring_rows(self) -> int:
    with self._lock:
      return sum(s.rows.shape[0] for s in self._slabs.values()
                 if s.rows is not None)

  # ------------------------------------------------------------- consumer

  def take(self, c: int):
    """Slab for chunk ``c``: ``(ids [cap] int32 sorted+INT32_MAX pads,
    rows [cap, F])``. Blocks up to ``timeout_s`` for the worker, then
    degrades to a synchronous gather of the same planned rows (counted
    in ``storage.prefetch_miss``) — identical bytes either way. Also
    submits the next chunk so the pipeline stays ``max_ahead`` deep."""
    with self._lock:
      slab = self._slabs.get(c)
    ok = slab is not None and slab.ready.wait(self.timeout_s)
    self._submit_next()
    if ok and slab.error is None and slab.ids is not None:
      return slab.ids, slab.rows
    # degraded path: the worker died, faulted, or is too slow — gather
    # the SAME planned rows on the dispatch thread. Never a wrong
    # batch, only a slower one.
    self.degraded = True
    rows_abs = self._planned_rows(c)
    metrics.inc('storage.prefetch_miss', int(rows_abs.shape[0]))
    return self._gather(rows_abs)

  def ack(self, c: int):
    """Chunk ``c``'s program has consumed its slab (the device_put
    copied it): free the ring slot."""
    with self._lock:
      self._slabs.pop(c, None)
      self.ack_t[c] = time.perf_counter()
    metrics.set_gauge('storage.ring_rows', self._ring_rows())
