"""RemoteScanTrainer: the chunk-staged hybrid — server-client epochs at
scanned speed.

The per-batch remote path (``RemoteDistNeighborLoader`` + a per-batch
jitted train step) pays >= 2 RPC dispatches plus host Python per
optimizer step, while the collocated ``DistScanTrainer`` runs
``ceil(steps/K) + 2`` dispatches per epoch. This module closes that gap
for the decoupled sampling-server/trainer topology (the reference's
flagship production deployment — PAPER.md "storage cluster != training
cluster"):

* **Servers produce K-batch blocks.** Each sampling server replays the
  SAME counter-addressed stream the per-batch mp-worker path draws
  (``distributed/block_producer.py``) and stacks K consecutive batches
  into one fixed-shape frame — the landing zone PyTorch-Direct (arXiv
  2101.07956) argues for: fixed-shape staging buffers for irregular
  remote payloads.
* **The client double-buffers blocks over RPC.** A bounded
  :class:`RemoteBlockStager` worker (the ``storage/staging.py``
  ChunkStager pattern) fetches block ``c+1`` while chunk ``c`` trains,
  and pipelines a ``block_produce`` for ``c+1`` ahead of the
  ``block_fetch`` of ``c`` so the server builds the next frame while
  this one's bytes are on the wire (the overlap posture of
  GPU-initiated direct storage access, arXiv 2306.16384).
* **One upload, one program per chunk.** The frame is device_put once
  (explicit — the epoch region runs under ``strict_guards``) and the
  chunk executes as ONE jitted ``lax.scan`` of the shared train step
  over the block's ``[k, ...]`` batches — one executable per (k, block
  shape) under GLT_STRICT; with ``wire_dtype='bf16'`` the f32 upcast
  happens inside the program (zero extra dispatches). Client dispatch
  budget: ``ceil(steps/K) + 2`` (begin + chunks + metrics concat),
  asserted by tests/test_remote_scan.py.
* **Acks and failover move to CHUNK granularity.** The PR 2 per-batch
  seed-ack protocol and the PR 10 FailoverRunner rollback contract
  unify here: a block is acked when its chunk is dispatched; a dead
  server's UNFETCHED blocks are re-replayed by survivors from the same
  counter stream, bit-identically (blocks are pure functions of the
  share + config + epoch + batch range). ``shuffle=True`` epochs fail
  over just as exactly: the server-side epoch permutation is
  EPOCH-ADDRESSED — a pure function of (stream seed, epoch), not a
  stateful host rng — so a survivor's replay producer draws the
  identical order (the constraint the per-batch loaders still carry;
  lifted here in round 15, chaos-tested for exact coverage under a
  mid-epoch kill). Frames already fetched client-side survive the
  death: a killed server loses at most the in-flight block.
* **Degrade-to-sync, never corruption.** A failed/slow stager worker
  falls back to a synchronous fetch of the SAME block on the dispatch
  thread (``remote.prefetch_miss``) — identical bytes, just slower,
  chaos-tested with the ``remote.block_fetch`` fault armed.

With ``shuffle=False`` and ``wire_dtype=None`` the losses and final
params are BIT-IDENTICAL to the per-batch remote path (single server,
``num_workers=1``) — including ragged tail batches, tail chunks and
epoch-2 stream continuation (tests/test_remote_scan.py pins all
three). The ``stage_hook``/``ack_hook`` chunk-boundary seams carry the
same contract as the other scanned trainers, so
``recovery.ChunkCheckpointer`` attaches unchanged and a crash resumes
at a block boundary (docs/remote_scan.md, docs/recovery.md).

Usage::

    glt.distributed.init_client(...)
    opts = glt.distributed.RemoteDistSamplingWorkerOptions(
        server_rank=[0, 1])
    trainer = glt.distributed.RemoteScanTrainer(
        [15, 10], seeds, model, tx, num_classes, batch_size=1024,
        chunk_size=32, worker_options=opts, seed=0)
    state, losses, accs = trainer.run_epoch(state)
"""
import dataclasses
import os
import queue
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from .. import metrics
from ..metrics import flight, programs, spans
from ..utils.faults import fault_point
from ..utils.strict import strict_guards
from ..utils.trace import record_dispatch
from .dist_loader import _norm_num_neighbors, _split_input_type
from .resilience import NO_RETRY, DeadlineExceeded, ServerDeadError
from .tenancy import with_backpressure

#: exception classes a block fetch may die with when its server is gone
#: (TCP reset, probe timeout, exhausted idempotent-retry deadline) —
#: anything else is a genuine remote error and must surface, not
#: trigger a bogus failover
_DEAD_EXCS = (ConnectionError, TimeoutError, OSError, DeadlineExceeded,
              ServerDeadError)


class _Slab:
  __slots__ = ('frame', 'ready', 'error', 't_done')

  def __init__(self):
    self.frame = None
    self.ready = threading.Event()
    self.error: Optional[BaseException] = None
    self.t_done: Optional[float] = None


class RemoteBlockStager:
  """One background worker prefetching block frames ahead of the chunk
  dispatch loop — the remote twin of ``storage.staging.ChunkStager``
  (same double-buffer shape, same degrade-to-sync failure semantics).

  ``fetch_fn(chunk_index)`` performs the actual RPC; it re-reads the
  trainer's schedule at call time, so a failover that re-points a
  chunk's descriptor at a survivor is picked up by both the worker and
  the synchronous fallback without re-priming the ring.

  Deliberately a SEPARATE class from ChunkStager rather than a shared
  parameterized base: the storage stager owns plan arrays, the tier
  gather + pad_slab, its own fault sites (storage.stage/promote) and
  the storage.* metric family, while this one owns RPC failure
  classes, schedule re-pointing and the remote.* family — the shared
  part is the lifecycle shape, and coupling the two hot paths would
  make every storage-side change a remote-side risk."""

  def __init__(self, fetch_fn: Callable[[int], dict], max_ahead: int = 2,
               timeout_s: float = 30.0):
    if max_ahead < 1:
      raise ValueError('max_ahead must be >= 1')
    self.fetch_fn = fetch_fn
    self.max_ahead = int(max_ahead)
    self.timeout_s = float(timeout_s)
    self._num_chunks = 0
    self._slabs: Dict[int, _Slab] = {}
    self._lock = threading.Lock()
    self._q: 'queue.Queue' = queue.Queue()
    self._worker: Optional[threading.Thread] = None
    self._stop = False
    self._next_submit = 0
    self.degraded = False   # a worker fetch failed this epoch
    self._ctx = None        # epoch-root span context adopted by _loop

  # ------------------------------------------------------------ lifecycle

  def begin_epoch(self, num_chunks: int, start_chunk: int = 0):
    """Install this epoch's chunk count and prime the first
    ``max_ahead`` fetches. A mid-epoch resume passes ``start_chunk``;
    consumed chunks are never fetched again."""
    if not 0 <= start_chunk <= num_chunks:
      raise ValueError(f'start_chunk={start_chunk} outside the '
                       f'{num_chunks}-chunk epoch')
    with self._lock:
      self._num_chunks = int(num_chunks)
      self._slabs = {}
      self._next_submit = int(start_chunk)
      self.degraded = False
      # capture the caller's (epoch-root) span context so worker-thread
      # fetch spans — remote.block_fetch, tenant.throttle — parent under
      # the epoch tree instead of floating as orphans
      self._ctx = spans.wire_context()
    self._ensure_worker()
    for _ in range(min(self.max_ahead, num_chunks - int(start_chunk))):
      self._submit_next()

  def close(self):
    self._stop = True
    self._q.put(None)
    w = self._worker
    if w is not None:
      w.join(timeout=5.0)
    self._worker = None
    self._stop = False
    # drain leftovers so a stale None can't kill the next epoch's
    # fresh worker on its first pop (the ChunkStager close contract)
    try:
      while True:
        self._q.get_nowait()
    except queue.Empty:
      pass

  def _ensure_worker(self):
    if self._worker is not None and self._worker.is_alive():
      return
    self._worker = threading.Thread(target=self._loop, daemon=True,
                                    name='glt-remote-block-stager')
    self._worker.start()

  def _submit_next(self):
    with self._lock:
      c = self._next_submit
      if c >= self._num_chunks:
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
        ctx = self._ctx
      if slab is None or slab.ready.is_set():
        continue   # epoch moved on, or failover already failed it
      try:
        t0 = time.perf_counter()
        fault_point('remote.block_fetch')
        with spans.adopt(ctx):
          slab.frame = self.fetch_fn(c)
        metrics.observe('remote.block_stage_ms',
                        (time.perf_counter() - t0) * 1e3)
      except BaseException as e:   # a chaos raise must not kill later blocks
        slab.error = e
        self.degraded = True
      finally:
        slab.t_done = time.perf_counter()
        slab.ready.set()

  # ------------------------------------------------------------- consumer

  def has_frame(self, c: int) -> bool:
    """True when chunk ``c``'s frame is already staged client-side —
    such frames survive the death of the server that produced them."""
    with self._lock:
      slab = self._slabs.get(c)
    return (slab is not None and slab.ready.is_set() and
            slab.error is None and slab.frame is not None)

  def fail_pending(self, chunks: List[int], exc: BaseException):
    """Failover support: mark not-yet-staged slabs errored so
    :meth:`take` falls through to the synchronous path (with the
    re-pointed descriptor) immediately instead of waiting out the
    timeout against a dead server."""
    with self._lock:
      slabs = [self._slabs.get(c) for c in chunks]
    for slab in slabs:
      if slab is not None and not slab.ready.is_set():
        slab.error = exc
        slab.ready.set()

  def take(self, c: int) -> dict:
    """Frame for chunk ``c``. Blocks up to ``timeout_s`` for the
    worker, then degrades to a synchronous fetch of the SAME block
    (``remote.prefetch_miss``) — identical bytes either way. The
    synchronous fetch may raise (dead server); the trainer's failover
    handles that and calls take() again — the ring advances only in
    :meth:`ack` (once per consumed chunk), so failover retries can
    never over-deepen the prefetch pipeline."""
    with self._lock:
      slab = self._slabs.get(c)
    ok = slab is not None and slab.ready.wait(self.timeout_s)
    if ok and slab.error is None and slab.frame is not None:
      return slab.frame
    self.degraded = True
    metrics.inc('remote.prefetch_miss')
    return self.fetch_fn(c)

  def ack(self, c: int):
    """Chunk ``c``'s program consumed its frame (the device_put
    copied it): free the ring slot and pull the next chunk forward so
    the pipeline stays ``max_ahead`` deep."""
    with self._lock:
      self._slabs.pop(c, None)
    self._submit_next()


def _group_frame(frame: dict, prefix: str, et_keyed: bool = False):
  """Host-side regroup of a typed block frame's dotted keys
  (``x.paper`` / ``row.paper__cites__paper`` — docs/capacity_plans.md)
  into a per-type dict for one device_put."""
  from ..typing import to_edge_type
  p = prefix + '.'
  return {(to_edge_type(kk[len(p):]) if et_keyed else kk[len(p):]):
          np.asarray(v) for kk, v in frame.items()
          if kk.startswith(p)}


class RemoteScanTrainer:
  """Scanned epochs over sampling-server block streams (module
  docstring). Scope: supervised node classification with collected
  features and labels, homogeneous or heterogeneous — typed seeds
  select typed block streams whose closed shapes come from the
  stream's CapacityPlan (docs/capacity_plans.md); the homo path is the
  single-ntype degenerate plan of the same machinery.

  Args:
    num_neighbors: fanouts (list, or per-etype dict for hetero).
    input_nodes: seed ids — untyped array, or ``('ntype', ids)`` for
      hetero graphs (split across the servers in rank order — the
      per-batch remote loaders' share convention).
    model, tx, num_classes: the supervised training triple
    batch_size: per optimizer step.
    chunk_size: K, batches per block/chunk (the tail block compiles
      once more at its own length).
    shuffle: epoch-addressed server-side shuffle — a pure function of
      (stream seed, epoch), so shuffled epochs keep exact chunk
      failover and resume. ``False`` additionally holds the
      bit-identity-to-the-per-batch-path contract (the per-batch
      loaders' host rng is stateful; docs/remote_scan.md).
    drop_last: drop the ragged tail batch.
    worker_options: RemoteDistSamplingWorkerOptions — server_rank,
      heartbeat/failover tunables, ``block_wire_dtype`` /
      ``block_ahead`` / ``block_timeout``.
    seed: sampling seed; folded per server exactly like the per-batch
      remote loaders (``seed * 7919 + i``).
  """

  _NAME = 'RemoteScanTrainer'

  # chunk-boundary hooks — the same host-side seam as the other scanned
  # trainers (docs/storage.md, docs/recovery.md): ``stage_hook(c,
  # start, k)`` before each chunk dispatch, ``ack_hook(c, start, k)``
  # right after (with ``self._chunk_carry`` exposing the boundary
  # state for the ChunkCheckpointer's explicit device_get)
  stage_hook = None
  ack_hook = None

  def __init__(self, num_neighbors, input_nodes, model, tx,
               num_classes: int, batch_size: int = 64,
               chunk_size: int = 32, shuffle: bool = False,
               drop_last: bool = False, collect_features: bool = True,
               worker_options=None, seed: Optional[int] = None):
    import jax

    from ..models import train as train_lib
    from ..sampler import SamplingConfig, SamplingType
    from . import dist_client
    from .resilience import Heartbeat
    if chunk_size < 1:
      raise ValueError(f'chunk_size must be >= 1, got {chunk_size}')
    input_type, input_nodes = _split_input_type(input_nodes)
    # typed seeds select the hetero block streams: the server derives
    # the stream's CapacityPlan (docs/capacity_plans.md) from the typed
    # share and the chunk program scans typed frames — the homo path is
    # the single-ntype degenerate case of the same machinery
    self._input_type = input_type
    if not collect_features:
      raise ValueError(f'{self._NAME} needs collect_features=True — '
                       'the chunk program trains on the block frames\' '
                       'feature payload')
    self.model = model
    self.tx = tx
    self.num_classes = num_classes
    self.chunk_size = int(chunk_size)
    self.batch_size = int(batch_size)
    self.input_seeds = np.asarray(input_nodes).reshape(-1)
    self.seed = seed
    self._shuffle = bool(shuffle)
    self._drop_last = bool(drop_last)
    opts = worker_options
    self._opts = opts
    self._dist_client = dist_client
    ranks = opts.server_rank if opts and opts.server_rank is not None \
        else [0]
    if isinstance(ranks, int):
      ranks = [ranks]
    self.server_ranks = list(ranks)
    self._wire_dtype = getattr(opts, 'block_wire_dtype', None) \
        if opts else None
    self._max_ahead = getattr(opts, 'block_ahead', 2) if opts else 2
    self._fetch_timeout = getattr(opts, 'block_timeout', 30.0) \
        if opts else 30.0
    self._failover_enabled = (opts.failover if opts else True)
    self._tenant = getattr(opts, 'tenant', None) if opts else None
    self._tenant_priority = getattr(opts, 'tenant_priority', None) \
        if opts else None
    self._tenant_weight = getattr(opts, 'tenant_weight', None) \
        if opts else None
    self._base_weight = float(self._tenant_weight or 1.0)
    self._bp_budget = getattr(opts, 'backpressure_budget', 120.0) \
        if opts else 120.0
    self._config = SamplingConfig(
        SamplingType.NODE, _norm_num_neighbors(num_neighbors),
        self.batch_size, self._shuffle, self._drop_last, False,
        collect_features, False, False, 'out', seed)
    base_key = (opts.worker_key if opts and opts.worker_key
                else f'rscan{os.getpid()}-{id(self):x}')
    self._worker_key = base_key
    # one block stream per server, shares + seed folding exactly as the
    # per-batch remote loaders split them (dist_loader.py) — with one
    # server and num_workers=1 the streams are bit-identical
    splits = np.array_split(self.input_seeds, len(self.server_ranks))
    self._streams = []
    for i, (rank, split) in enumerate(zip(self.server_ranks, splits)):
      from ..sampler import NodeSamplerInput
      share = (NodeSamplerInput(split, input_type=self._input_type)
               if self._input_type is not None else split)
      cfg_i = dataclasses.replace(self._config,
                                  seed=(seed or 0) * 7919 + i)
      pid = with_backpressure(
          lambda rank=rank, share=share, cfg_i=cfg_i, i=i:
          dist_client.request_server(
              rank, 'create_block_producer', share, cfg_i,
              self._wire_dtype, worker_key=f'{base_key}/blk/{i}',
              idempotent=True, **self._tenant_kwargs()),
          describe=f'create_block_producer stream {i} rank {rank}',
          budget_s=self._bp_budget, tenant=self._tenant)
      nb = dist_client.request_server(
          rank, 'block_producer_num_batches', pid, idempotent=True)
      self._streams.append(dict(rank=rank, pid=pid, seeds=share,
                                cfg=cfg_i, num_batches=int(nb)))
    self._active_ranks = list(self.server_ranks)
    self._current_chunk = -1
    self._dead_ranks: Dict[int, str] = {}
    self._replay_pids: Dict[tuple, int] = {}
    self._epochs = 0
    self._schedule: List[dict] = []
    self._stager = RemoteBlockStager(self._fetch_block,
                                     max_ahead=self._max_ahead,
                                     timeout_s=self._fetch_timeout)
    hb_interval = opts.heartbeat_interval if opts else 1.0
    hb_miss = opts.heartbeat_miss if opts else 3

    def probe(rank):
      dist_client.request_server(rank, 'heartbeat',
                                 timeout=max(hb_interval, 2.0),
                                 idempotent=True, retry_policy=NO_RETRY)

    self._heartbeat = Heartbeat(self.server_ranks, probe,
                                interval=hb_interval,
                                miss_threshold=hb_miss)
    self._hb_started = False
    self._train_step, _ = train_lib.make_train_step(model, tx,
                                                    num_classes)
    self._begin_fn = programs.instrument(self._build_begin_fn(),
                                         'remote_epoch_begin')
    self._chunk_fn = programs.instrument(self._build_chunk_fn(),
                                         'remote_scan_chunk')
    self._concat_fn = programs.instrument(self._build_concat_fn(),
                                          'remote_metrics_concat')
    self.last_overflow = None       # [bool] device scalar, per epoch
    self.last_epoch_seed_ids = None  # host ack record, per epoch

  # ------------------------------------------------------------- programs

  def _build_begin_fn(self):
    """ONE prologue program committing the epoch carry (train state +
    overflow flag) into the canonical device layout the chunk
    executable expects — a host-built or restored state then presents
    the same signature as a donated chunk output, so no epoch's first
    chunk retraces."""
    import jax

    def remote_epoch_begin(state, ovf):
      return state, ovf

    return jax.jit(remote_epoch_begin)

  def _build_chunk_fn(self):
    """The scanned K-step block program: ``lax.scan`` of the shared
    train step over the uploaded block's per-step batches. The wire
    upcast (bf16 -> f32) happens INSIDE the program, and every block
    buffer is donated — HBM stays flat at one state + one in-flight
    block."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    train_step = self._train_step   # jit-of-jit: inlined into the scan
    upcast = self._wire_dtype is not None

    def remote_scan_chunk(state, ovf, x, row, col, edge_mask, y, nseed,
                          ovf_steps):
      def body(carry, xs):
        state, ovf = carry
        x_s, r_s, c_s, em_s, y_s, ns_s, o_s = xs
        up = (lambda a: a.astype(jnp.float32)) if upcast else (lambda a: a)
        if isinstance(x_s, dict):
          # typed block frame (docs/capacity_plans.md): per-ntype
          # feature dicts, per-etype edge dicts — the same batch dict
          # the collocated hetero collate builds (loader/pipeline.py)
          batch = dict(x={t: up(v) for t, v in x_s.items()},
                       edge_index={et: jnp.stack([r_s[et], c_s[et]])
                                   for et in r_s},
                       edge_mask=em_s, y=y_s, num_seed_nodes=ns_s)
        else:
          batch = dict(x=up(x_s), edge_index=jnp.stack([r_s, c_s]),
                       edge_mask=em_s, y=y_s, num_seed_nodes=ns_s)
        state, loss, acc = train_step(state, batch)
        return (state, ovf | o_s), (loss, acc)

      (state, ovf), (losses, accs) = lax.scan(
          body, (state, ovf),
          (x, row, col, edge_mask, y, nseed, ovf_steps))
      return state, ovf, losses, accs

    # donate the carry only: the block buffers have no same-shaped
    # outputs to alias into (XLA would warn and copy), and they free
    # naturally when the chunk's Python references drop
    return jax.jit(remote_scan_chunk, donate_argnums=(0, 1))

  def _build_concat_fn(self):
    """One program concatenating the per-chunk [k] loss/acc outputs."""
    import jax
    import jax.numpy as jnp

    def remote_metrics_concat(losses, accs):
      return jnp.concatenate(losses), jnp.concatenate(accs)

    return jax.jit(remote_metrics_concat)

  # ------------------------------------------------------------- schedule

  def __len__(self) -> int:
    return sum(st['num_batches'] for st in self._streams)

  def _block_boundaries(self) -> List[int]:
    """Global step indices where blocks begin — the only valid
    ``start_step`` resume points (with one server: multiples of K)."""
    bounds, step0 = [], 0
    for st in self._streams:
      nb = st['num_batches']
      bounds.extend(step0 + b for b in range(0, nb, self.chunk_size))
      step0 += nb
    return bounds

  def _block_schedule(self, steps: int, epoch: int) -> List[dict]:
    """Chunk descriptors in epoch order: stream shares back to back
    (concatenated shares == the full seed sequence for shuffle=False),
    each stream cut into K-batch blocks plus a tail. A rank already
    known dead is re-pointed at survivors up front (the epoch-start
    failover path)."""
    descs, step0 = [], 0
    for i, st in enumerate(self._streams):
      nb = st['num_batches']
      for b0 in range(0, nb, self.chunk_size):
        gstep = step0 + b0
        if gstep >= steps:
          break
        k = min(self.chunk_size, nb - b0, steps - gstep)
        descs.append(dict(stream=i, rank=st['rank'], pid=st['pid'],
                          epoch=epoch, start=b0, k=k, step0=gstep))
      step0 += nb
    if self._dead_ranks:
      survivors = [r for r in self._active_ranks
                   if r not in self._dead_ranks] or \
                  [r for r in self.server_ranks
                   if r not in self._dead_ranks]
      if not survivors:
        raise RuntimeError('no live sampling server to start the '
                           f'epoch: dead={self._dead_ranks}')
      moved = 0
      for d in descs:
        if d['rank'] in self._dead_ranks:
          self._require_failover()
          surv = survivors[moved % len(survivors)]
          d['pid'] = self._replay_pid(surv, d['stream'])
          d['rank'] = surv
          moved += 1
    # policy shrink (set_block_ranks / set_tenant_weight): home ranks
    # outside the active set hand their blocks to replay producers on
    # active ranks — the same counter-addressed contract as failover,
    # driven by policy instead of death
    inactive = [r for r in self.server_ranks
                if r not in self._active_ranks and
                r not in self._dead_ranks]
    if inactive:
      targets = [r for r in self._active_ranks
                 if r not in self._dead_ranks]
      moved = 0
      for d in descs:
        if d['rank'] in inactive:
          tgt = targets[moved % len(targets)]
          d['pid'] = self._replay_pid(tgt, d['stream'])
          d['rank'] = tgt
          moved += 1
      if moved:
        metrics.inc('tenant.rebalanced_blocks', moved)
    return descs

  # -------------------------------------------------------- block fetch

  def _fetch_block(self, c: int) -> dict:
    """Fetch chunk ``c``'s frame (reading the schedule AT CALL TIME so
    failover re-pointing is honored), pipelining a produce of the next
    pending chunk so the server builds c+1 while c's bytes are on the
    wire. Called from the stager worker AND from its synchronous
    degrade path."""
    desc = self._schedule[c]
    nxt = c + 1
    if nxt < len(self._schedule) and not self._stager.has_frame(nxt):
      nd = self._schedule[nxt]
      try:
        fut = self._dist_client.async_request_server(
            nd['rank'], 'block_produce', nd['pid'], nd['epoch'],
            nd['start'], nd['k'])
        fut.add_done_callback(lambda f: f.exception())  # swallow
      except Exception:   # produce-ahead is an optimization only
        pass
    t0 = time.perf_counter()
    with spans.span('remote.block_fetch', chunk=int(c),
                    rank=int(desc['rank']), start=int(desc['start'])):
      frame = with_backpressure(
          lambda: self._dist_client.request_server(
              desc['rank'], 'block_fetch', desc['pid'], desc['epoch'],
              desc['start'], desc['k'], idempotent=True),
          describe=f'block_fetch chunk {c} rank {desc["rank"]}',
          budget_s=self._bp_budget, tenant=self._tenant)
    metrics.observe('remote.block_fetch_ms',
                    (time.perf_counter() - t0) * 1e3)
    nbytes = sum(int(np.asarray(v).nbytes) for v in frame.values())
    metrics.inc('remote.blocks')
    metrics.inc('remote.block_bytes', nbytes)
    metrics.observe('remote.block_mb_per_chunk', nbytes / 1e6)
    return frame

  # ----------------------------------------------------------- failover

  def _require_failover(self):
    # shuffle=True is failover-safe on THIS path (unlike the per-batch
    # remote loaders): the server permutation is epoch-addressed — a
    # pure function of (stream seed, epoch), block_producer._epoch_order
    # — so a survivor's replay producer draws the identical order and
    # re-replays the dead rank's blocks bit-identically
    # (tests/test_remote_scan.py pins exact coverage after a mid-epoch
    # kill with shuffle=True)
    if not self._failover_enabled:
      raise RuntimeError(
          'sampling server died and failover is disabled '
          '(RemoteDistSamplingWorkerOptions.failover=False)')

  def _replay_pid(self, survivor: int, stream_i: int) -> int:
    """A block producer for stream ``stream_i``'s share ON the
    survivor — same share, same folded config, so its blocks are
    bit-identical to the dead server's. worker_key makes the create
    retry-safe."""
    key = (survivor, stream_i)
    pid = self._replay_pids.get(key)
    if pid is not None:
      return pid
    st = self._streams[stream_i]
    pid = with_backpressure(
        lambda: self._dist_client.request_server(
            survivor, 'create_block_producer', st['seeds'], st['cfg'],
            self._wire_dtype,
            worker_key=f'{self._worker_key}/bfo/s{stream_i}/r{survivor}',
            idempotent=True, **self._tenant_kwargs()),
        describe=f'replay producer stream {stream_i} rank {survivor}',
        budget_s=self._bp_budget, tenant=self._tenant)
    self._replay_pids[key] = pid
    return pid

  # ------------------------------------------------- tenancy / elasticity

  def _tenant_kwargs(self) -> dict:
    """create_block_producer kwargs registering this trainer's streams
    under its tenant — empty (and wire-compatible with pre-tenancy
    servers) when no tenant is configured."""
    if self._tenant is None:
      return {}
    return dict(tenant=self._tenant, priority=self._tenant_priority,
                weight=self._tenant_weight)

  def set_block_ranks(self, ranks: List[int]):
    """Elastic resize: restrict block production to ``ranks`` (grow by
    passing a superset again). Mid-epoch, pending not-yet-staged chunks
    whose home rank left the active set are re-pointed at replay
    producers on active ranks — the PR 11 counter-addressed contract
    makes the re-produced blocks bit-identical, so this is failover
    machinery driven by policy instead of death."""
    live = [r for r in dict.fromkeys(ranks) if r not in self._dead_ranks]
    unknown = [r for r in live if r not in self.server_ranks]
    if unknown:
      raise ValueError(f'unknown server ranks {unknown}; trainer knows '
                       f'{self.server_ranks}')
    if not live:
      raise ValueError('set_block_ranks needs at least one live rank '
                       f'(dead={self._dead_ranks})')
    self._active_ranks = live
    if not self._schedule:
      return
    moved = 0
    for j in range(self._current_chunk + 1, len(self._schedule)):
      d = self._schedule[j]
      if (d['rank'] in self._active_ranks or
          d['rank'] in self._dead_ranks or self._stager.has_frame(j)):
        continue
      tgt = self._active_ranks[moved % len(self._active_ranks)]
      d['pid'] = self._replay_pid(tgt, d['stream'])
      d['rank'] = tgt
      moved += 1
    if moved:
      metrics.inc('tenant.rebalanced_blocks', moved)

  def set_tenant_weight(self, weight: float):
    """Autoscale on a weight change: push the new fair-share weight to
    every live server, then grow/shrink the active producer rank set
    proportionally (weight halved -> half the ranks produce for this
    tenant; blocks stay bit-identical under the re-point)."""
    if weight <= 0:
      raise ValueError(f'tenant weight must be > 0, got {weight}')
    if self._tenant is not None:
      for r in self.server_ranks:
        if r in self._dead_ranks:
          continue
        try:
          self._dist_client.request_server(
              r, 'update_tenant', self._tenant, weight=float(weight),
              idempotent=True)
        except _DEAD_EXCS:
          pass   # heartbeat will declare it; re-point happens there
    self._tenant_weight = float(weight)
    live = [r for r in self.server_ranks if r not in self._dead_ranks]
    frac = min(1.0, float(weight) / max(self._base_weight, 1e-9))
    target = max(1, int(np.ceil(frac * len(live))))
    self.set_block_ranks(live[:target])

  def _handle_dead_rank(self, rank: int, cause: str, ci: int):
    """Declare ``rank`` dead and re-point its pending (unfetched)
    blocks at survivors — frames already staged client-side are kept
    (the data outlives its producer), so a killed server costs at most
    the in-flight block. Idempotent per rank."""
    if rank in self._dead_ranks:
      return
    from ..utils import trace
    pending = [j for j in range(ci, len(self._schedule))
               if self._schedule[j]['rank'] == rank and
               not self._stager.has_frame(j)]
    if pending:
      # feasibility FIRST: when this epoch cannot fail over, the rank
      # must not be marked sticky-dead (the per-batch loaders' rule)
      self._require_failover()
    self._dead_ranks[rank] = str(cause)
    self._heartbeat.mark_dead(rank, cause)
    if not pending:
      return
    survivors = [r for r in self.server_ranks
                 if r not in self._dead_ranks]
    if not survivors:
      raise RuntimeError(
          f'all sampling servers dead (last: rank {rank}: {cause}) — '
          'cannot complete the epoch')
    fo_span = spans.begin('loader.failover', rank=rank,
                          cause=str(cause)[:200], blocks=len(pending),
                          detected_chunk=int(ci),
                          survivors=list(survivors))
    try:
      for n, j in enumerate(pending):
        surv = survivors[n % len(survivors)]
        d = self._schedule[j]
        d['pid'] = self._replay_pid(surv, d['stream'])
        d['rank'] = surv
      trace.counter_inc('resilience.failover')
      metrics.inc('remote.failover_blocks', len(pending))
      import logging
      logging.getLogger('graphlearn_tpu.loader').warning(
          'sampling server rank %d dead (%s): re-replaying %d pending '
          'blocks on survivors %s', rank, cause, len(pending),
          survivors)
    except BaseException as e:
      fo_span.attrs['error'] = f'{type(e).__name__}: {e}'
      raise
    finally:
      spans.end(fo_span)
    self._stager.fail_pending(
        pending, ConnectionError(f'rank {rank} dead: {cause}'))

  def _poll_liveness(self, ci: int):
    for rank, cause in self._heartbeat.dead_ranks().items():
      if rank not in self._dead_ranks:
        self._handle_dead_rank(rank, cause, ci)

  def _take_with_failover(self, ci: int) -> dict:
    """take() with dead-server recovery: each failure declares the
    current owner dead and re-points the chunk at a survivor; bounded
    by the server count."""
    for _ in range(len(self.server_ranks) + 1):
      try:
        return self._stager.take(ci)
      except _DEAD_EXCS as e:
        self._handle_dead_rank(self._schedule[ci]['rank'], repr(e), ci)
    raise RuntimeError(f'chunk {ci}: no server could deliver its '
                       f'block (dead={self._dead_ranks})')

  # ----------------------------------------------------------------- epoch

  def run_epoch(self, state, max_steps: Optional[int] = None,
                start_step: int = 0, resume_overflow: bool = False):
    """One chunk-staged remote epoch. Returns ``(state, losses,
    accs)`` with losses/accs [steps]-shaped device arrays — fetch
    once, after the epoch. The input state is DONATED to the first
    chunk; train on the returned state. ``start_step`` (a block
    boundary) resumes THIS epoch mid-flight — go through
    ``recovery.ChunkCheckpointer.resume_epoch``."""
    import jax.numpy as jnp
    if not self._hb_started:
      self._heartbeat.start()
      self._hb_started = True
    epoch_no = self._epochs
    full_steps = len(self)
    steps = full_steps
    truncated = False
    if max_steps is not None and max_steps < steps:
      steps, truncated = max_steps, True
    if start_step:
      if start_step not in set(self._block_boundaries()):
        raise ValueError(f'start_step={start_step} is not a block '
                         f'boundary (chunk_size={self.chunk_size}) — '
                         'resume only at the boundaries checkpoints '
                         'are taken at')
      if not 0 <= start_step < steps:
        raise ValueError(f'start_step={start_step} outside this '
                         f"epoch's {steps} steps")
    # both brackets open after the step arithmetic (and the zero-step
    # path's empty-result device work): a prologue raise must not
    # leave a permanently-open flight record or a leaked attached span
    # — see ScanTrainer.run_epoch
    if steps <= 0:
      empty = jnp.zeros((0,), jnp.float32)
      flight_tok = flight.epoch_begin()
      epoch_span = spans.begin('epoch.run', emitter=self._NAME,
                               epoch=epoch_no)
      spans.end(epoch_span, steps=0, completed=True)
      flight.epoch_end(flight_tok, emitter=self._NAME, epoch=epoch_no,
                       steps=0, config=self._flight_config(),
                       extra={'chunk_size': self.chunk_size,
                              'truncated': truncated})
      return state, empty, empty

    flight_tok = flight.epoch_begin()
    epoch_span = spans.begin('epoch.run', emitter=self._NAME,
                             epoch=epoch_no)
    completed = False
    self._steps_dispatched = start_step
    try:
      state, losses, accs, ovf = self._run_epoch_body(
          state, steps, full_steps, start_step=start_step,
          resume_overflow=resume_overflow)
      completed = True
      self.last_overflow = ovf
    finally:
      spans.end(epoch_span,
                steps=(steps if completed else
                       getattr(self, '_steps_dispatched', 0)),
                completed=completed)
      flight.epoch_end(flight_tok, emitter=self._NAME, epoch=epoch_no,
                       steps=(steps if completed else
                              getattr(self, '_steps_dispatched', 0)),
                       completed=completed,
                       config=self._flight_config(),
                       extra={'chunk_size': self.chunk_size,
                              'truncated': truncated,
                              'start_step': start_step,
                              'dead_ranks': {str(r): c for r, c in
                                             self._dead_ranks.items()}})
    return state, losses, accs

  def _run_epoch_body(self, state, steps, full_steps, start_step=0,
                      resume_overflow=False):
    import jax
    epoch = self._epochs
    self._schedule = self._block_schedule(steps, epoch)
    start_idx = 0
    if start_step:
      start_idx = next(i for i, d in enumerate(self._schedule)
                       if d['step0'] == start_step)
    self._current_chunk = start_idx - 1
    self._seen_ids: List[np.ndarray] = []
    self._stager.begin_epoch(len(self._schedule), start_chunk=start_idx)
    losses, accs = [], []
    with strict_guards():
      record_dispatch('remote_epoch_begin')
      state, ovf = self._begin_fn(
          jax.device_put(state),
          jax.device_put(np.asarray(bool(resume_overflow))))
      for ci in range(start_idx, len(self._schedule)):
        self._current_chunk = ci   # elastic re-points only chunks > ci
        desc = self._schedule[ci]
        if self.stage_hook is not None:
          self.stage_hook(ci, desc['step0'], desc['k'])
        self._poll_liveness(ci)
        frame = self._take_with_failover(ci)
        blk = self._upload(frame)
        record_dispatch('remote_scan_chunk')
        with spans.span('epoch.chunk', start=desc['step0'],
                        k=desc['k']):
          state, ovf, loss_k, acc_k = self._chunk_fn(state, ovf, *blk)
        # the device_put copied the frame: free the ring slot and keep
        # the host-side seed ack (the chunk-granular ack protocol)
        self._stager.ack(ci)
        self._ack_frame(frame)
        losses.append(loss_k)
        accs.append(acc_k)
        self._steps_dispatched = desc['step0'] + desc['k']
        if self.ack_hook is not None:
          # boundary carry for the recovery seam — valid only inside
          # the hook call (the next chunk dispatch donates state/ovf)
          self._chunk_carry = dict(state=state, ovf=ovf, losses=losses,
                                   accs=accs, steps=steps,
                                   full_steps=full_steps,
                                   start_step=start_step)
          self.ack_hook(ci, desc['step0'], desc['k'])
      if len(losses) > 1:
        record_dispatch('remote_metrics_concat')
        losses, accs = self._concat_fn(losses, accs)
      else:
        losses, accs = losses[0], accs[0]
    self.last_epoch_seed_ids = (
        np.concatenate(self._seen_ids) if self._seen_ids
        else np.zeros((0,), np.int64))
    self._epochs += 1
    return state, losses, accs, ovf

  def _upload(self, frame: dict):
    """One explicit device upload of the block's training payload —
    the epoch region runs under strict_guards, so nothing may arrive
    implicitly. Typed frames (dotted keys, docs/capacity_plans.md)
    upload as per-ntype / per-etype dicts in one device_put."""
    import jax
    if self._input_type is not None:
      t_in = self._input_type
      x = _group_frame(frame, 'x')
      row = _group_frame(frame, 'row', True)
      col = _group_frame(frame, 'col', True)
      em = _group_frame(frame, 'edge_mask', True)
      y = np.asarray(frame[f'y.{t_in}'])
      nseed = np.asarray(
          frame[f'num_sampled_nodes.{t_in}'])[:, 0].astype(np.int32)
      k = int(y.shape[0])
      ovf_steps = np.asarray(frame.get(
          '#META.overflow', np.zeros((k,), bool))).astype(bool)
      return jax.device_put((x, row, col, em, y, nseed, ovf_steps))
    k = int(np.asarray(frame['row']).shape[0])
    ovf_steps = np.asarray(frame.get('#META.overflow',
                                     np.zeros((k,), bool))).astype(bool)
    nseed = np.asarray(frame['num_sampled_nodes'])[:, 0].astype(np.int32)
    return jax.device_put((
        np.asarray(frame['x']), np.asarray(frame['row']),
        np.asarray(frame['col']), np.asarray(frame['edge_mask']),
        np.asarray(frame['y']), nseed, ovf_steps))

  def _ack_frame(self, frame: dict):
    """Host-side seed ack at CHUNK granularity: record the seed ids
    this block delivered (the per-batch ack protocol's provenance,
    lifted to the block) — chaos tests assert exact coverage from
    this. Typed frames ack from the seed type's 'batch.<t>' key."""
    ids = frame.get('batch')
    if ids is None and self._input_type is not None:
      ids = frame.get(f'batch.{self._input_type}')
    if ids is None:
      return
    ids = np.asarray(ids)
    bs = frame.get('#META.batch_size')
    if bs is not None:
      bs = np.asarray(bs).reshape(-1)
      rows = [ids[j][:int(bs[j])] for j in range(ids.shape[0])]
      ids = np.concatenate(rows) if rows else ids.reshape(-1)
    else:
      ids = ids.reshape(-1)
    self._seen_ids.append(np.asarray(ids, np.int64).reshape(-1))

  # -------------------------------------------------------------- config

  def _flight_config(self) -> dict:
    fans = self._config.num_neighbors
    return dict(trainer=self._NAME, batch_size=self.batch_size,
                chunk_size=self.chunk_size,
                input_type=self._input_type,
                fanouts=(dict(fans) if isinstance(fans, dict)
                         else list(fans)),
                shuffle=self._shuffle, drop_last=self._drop_last,
                num_classes=self.num_classes, seed=self.seed,
                servers=list(self.server_ranks),
                wire_dtype=self._wire_dtype,
                tenant=self._tenant,
                tenant_priority=self._tenant_priority,
                tenant_weight=self._tenant_weight,
                active_ranks=list(self._active_ranks))

  # -------------------------------------------------- recovery protocol
  # (recovery/checkpoint.py ChunkCheckpointer — docs/recovery.md). The
  # client carries NO sampler: the server streams are counter-addressed
  # by (epoch, batch index) alone, so a snapshot needs only the epoch
  # index beyond the train state — the resumed epoch re-fetches its
  # remaining blocks from the same pure stream.

  def _recovery_config(self) -> dict:
    import hashlib
    cfg = self._flight_config()
    # elastic tenancy state changes mid-run by design; it must not
    # invalidate the snapshot fingerprint
    cfg.pop('active_ranks', None)
    cfg.pop('tenant_weight', None)
    cfg.update(
        collect_features=self._config.collect_features,
        seeds_sha=hashlib.sha1(
            np.ascontiguousarray(
                self.input_seeds.astype(np.int64)).tobytes())
        .hexdigest()[:16])
    return cfg

  def _recovery_capture(self, carry):
    del carry
    return {}, {}

  def _recovery_load(self, meta, arrays):
    del arrays
    self._epochs = int(meta['epoch'])

  def _recovery_advance(self, meta):
    self._epochs = int(meta['epoch']) + 1

  # ------------------------------------------------------------ teardown

  def shutdown(self):
    """Stop the stager/heartbeat and destroy the server-side block
    producers (dead ranks skipped; destroys are idempotent)."""
    self._stager.close()
    self._heartbeat.stop()
    targets = [(st['rank'], st['pid']) for st in self._streams]
    targets += [(rank, pid)
                for (rank, _), pid in self._replay_pids.items()]
    for rank, pid in targets:
      if rank in self._dead_ranks:
        continue
      try:
        self._dist_client.request_server(rank, 'destroy_block_producer',
                                         pid)
      except (RuntimeError, ConnectionError, OSError):
        pass
