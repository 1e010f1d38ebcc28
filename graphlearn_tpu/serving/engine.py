"""Low-latency online serving endpoint: admission batching into
calibrated static-shape buckets.

"Millions of users" means many small concurrent lookups, while the
accelerator wants few large fixed-shape dispatches — the classic
serving impedance mismatch. ``ServingEngine`` resolves it the TPU way
(GNNSampler, arxiv 2108.11571: hardware-matched static shapes):

  * **Admission batching.** Concurrent requests enqueue; the dispatcher
    drains them into one flat id vector, waiting at most
    ``max_wait_ms`` past the first request for the batch to fill — the
    standard latency/throughput knob.
  * **Calibrated padded buckets.** The batch pads to the SMALLEST
    capacity from a closed ``buckets`` set, so one persistent jitted
    program per bucket serves all traffic — no per-request compiles,
    ever. Oversized batches split across the largest bucket.
  * **Hot-embedding cache.** The store side (serving/store.py) answers
    from the materialized table — single-replica HBM, or the
    DistFeature-backed sharded store whose replicated hot split is the
    hot-embedding cache (docs/feature_cache.md machinery, reused).
  * **Staleness + final-layer refresh.** ``mark_stale(ids)`` flags
    nodes whose inputs changed; before a stale node is served, the
    engine recomputes ONLY its last layer from the penultimate store
    (``EmbeddingMaterializer.refresh_rows`` — the same training forward
    slice) and writes the rows back. Everything else keeps serving from
    the table.

Instrumented end to end through the PR 6 registry: per-request
``serving.queue_wait_ms`` / ``serving.total_ms`` histograms,
per-batch ``serving.batch_fill`` /
``serving.compute_ms``, and ``serving.requests`` / ``serving.batches``
/ ``serving.refreshed`` counters. The remote entry point
(``DistServer.serve``) is read-only and idempotent, so clients retry it
under the fault registry exactly like ``get_metrics`` —
chaos-hardening comes from the PR 2 machinery, not new code.
"""
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, Optional, Sequence

import numpy as np

from .. import metrics
from ..metrics import spans

DEFAULT_BUCKETS = (16, 64, 256)


class _Request:
  __slots__ = ('ids', 'future', 't0', 'span')

  def __init__(self, ids: np.ndarray):
    self.ids = ids
    self.future: Future = Future()
    self.t0 = time.perf_counter()
    # the request span opens on the SUBMITTING thread (so it inherits
    # the caller's trace — e.g. the serve-RPC handler's adopted client
    # context) but is closed by the dispatcher at respond time:
    # attach=False keeps it off the submitter's context stack
    self.span = spans.begin('serving.request', attach=False,
                            n=int(ids.size))


class ServingEngine:
  """Admission-batched embedding lookup endpoint over an embedding
  store.

  Args:
    store: ``EmbeddingStore`` or ``DistEmbeddingStore``.
    buckets: ascending padded capacities (each a multiple of the
      store's ``granularity``); the closed static-shape set.
    max_wait_ms: admission window past the first queued request.
    refresh_fn: ``ids -> [n, F] rows`` final-layer recompute
      (``EmbeddingMaterializer.refresh_rows``) for stale nodes;
      requires a store with ``update_rows`` (the single-replica store).
    config: a tune artifact (``graphlearn_tpu.tune()``,
      docs/tuning.md): supplies the calibrated bucket ladder when
      ``buckets`` is not given explicitly, and refuses a store whose
      node count drifted from the tuned dataset's.
  """

  def __init__(self, store, buckets: Optional[Sequence[int]] = None,
               max_wait_ms: float = 2.0,
               refresh_fn: Optional[Callable] = None, config=None):
    if config is not None:
      tuned_n = (config.dataset or {}).get('num_nodes')
      store_n = getattr(store, 'num_nodes', None)
      if tuned_n is not None and store_n is not None and \
          int(tuned_n) != int(store_n):
        raise ValueError(
            f'ServingEngine config= artifact was tuned for '
            f'{tuned_n} nodes but the store serves {store_n} — '
            'dataset drifted; re-run graphlearn_tpu.tune() '
            f'(artifact fingerprint {config.fingerprint}, '
            'docs/tuning.md)')
      if buckets is None:
        buckets = config.serving_kwargs()['buckets']
      if hasattr(config, 'apply_kernel_routing'):
        # the tuned gather-kernel choice reaches the engine's store
        # (EmbeddingStore.set_kernel_routing); stores without the
        # surface (dist/tiered) simply don't accept it
        config.apply_kernel_routing(store)
    if buckets is None:
      buckets = DEFAULT_BUCKETS
    buckets = tuple(sorted(int(b) for b in set(buckets)))
    if not buckets:
      raise ValueError('at least one bucket capacity is required')
    g = getattr(store, 'granularity', 1)
    for b in buckets:
      if b <= 0 or b % g:
        raise ValueError(
            f'bucket capacity {b} must be a positive multiple of the '
            f'store granularity {g}')
    if refresh_fn is not None and \
        getattr(store, 'update_rows', None) is None:
      raise ValueError('refresh_fn needs a store with update_rows '
                       '(single-replica EmbeddingStore)')
    if refresh_fn is not None:
      try:
        store.update_rows(np.zeros((0,), np.int64),
                          np.zeros((0, store.feature_dim), np.float32))
      except NotImplementedError:
        raise ValueError(
            'refresh_fn is unsupported on immutable stores — refresh '
            'on the materializing replica and rebuild (docs/serving.md)')
    self.store = store
    self.buckets = buckets
    self.max_wait_s = float(max_wait_ms) / 1e3
    self._refresh_fn = refresh_fn
    self._q: 'queue.Queue[_Request]' = queue.Queue()
    # stale-id set shared between caller threads (mark_stale) and the
    # serving thread (_refresh_stale) — every access holds _stale_lock
    # graftlint: shared[_stale_lock]
    self._stale: set = set()
    self._stale_lock = threading.Lock()
    self._stop = threading.Event()
    self._thread: Optional[threading.Thread] = None

  # ------------------------------------------------------------ lifecycle

  def start(self):
    if self._thread is not None and self._thread.is_alive():
      return self
    self._stop.clear()
    self._thread = threading.Thread(target=self._loop, daemon=True,
                                    name='glt-serving-dispatcher')
    self._thread.start()
    return self

  def stop(self):
    """Drain-free stop: pending requests get a RuntimeError (callers
    hold Futures, nothing blocks forever)."""
    self._stop.set()
    if self._thread is not None:
      self._thread.join(timeout=10)
      self._thread = None
    while True:
      try:
        r = self._q.get_nowait()
      except queue.Empty:
        break
      if not r.future.done():
        r.future.set_exception(RuntimeError('serving engine stopped'))
        spans.end(r.span, error='stopped')

  def __enter__(self):
    return self.start()

  def __exit__(self, *exc):
    self.stop()

  # -------------------------------------------------------------- intake

  def submit(self, ids) -> Future:
    """Enqueue one lookup request (any length). Returns a Future whose
    result is the [len(ids), F] numpy row block, in request order."""
    if self._thread is None or not self._thread.is_alive():
      raise RuntimeError('serving engine is not started')
    ids = np.asarray(ids, np.int64).reshape(-1)
    if ids.size == 0:
      f = Future()
      f.set_result(np.zeros((0, self.store.feature_dim), np.float32))
      return f
    if ids.min() < 0 or ids.max() >= self.store.num_nodes:
      raise ValueError(
          f'ids must be in [0, {self.store.num_nodes}); padding is an '
          'engine-internal concept and never crosses the API')
    req = _Request(ids)
    self._q.put(req)
    if self._stop.is_set() and not req.future.done():
      # stop() may have drained the queue between the alive check and
      # our put — fail fast instead of leaving the Future to hang
      req.future.set_exception(RuntimeError('serving engine stopped'))
      spans.end(req.span, error='stopped')
    return req.future

  def lookup(self, ids, timeout: Optional[float] = 30.0) -> np.ndarray:
    """Synchronous convenience: submit + wait."""
    return self.submit(ids).result(timeout)

  def mark_stale(self, ids):
    """Flag nodes whose features/neighborhood changed: their next
    lookup pays one final-layer refresh, then serves fresh rows.
    Requires a ``refresh_fn`` — without one a mark could never be
    honored, so accepting it would silently serve stale rows forever
    (rematerialize + rebuild instead, docs/serving.md)."""
    if self._refresh_fn is None:
      raise ValueError(
          'mark_stale needs a refresh_fn (ServingEngine(..., '
          'refresh_fn=materializer.refresh_rows)); without one stale '
          'marks would be accepted but never honored — rematerialize '
          'and rebuild the store instead (docs/serving.md)')
    ids = np.asarray(ids, np.int64).reshape(-1)
    with self._stale_lock:
      self._stale.update(int(i) for i in ids)

  def stale_count(self) -> int:
    with self._stale_lock:
      return len(self._stale)

  # ----------------------------------------------------------- dispatcher

  def _bucket_for(self, n: int) -> int:
    for b in self.buckets:
      if n <= b:
        return b
    return self.buckets[-1]

  def _loop(self):
    while not self._stop.is_set():
      try:
        first = self._q.get(timeout=0.05)
      except queue.Empty:
        continue
      batch = [first]
      fill = first.ids.size
      deadline = first.t0 + self.max_wait_s
      while fill < self.buckets[-1]:
        remaining = deadline - time.perf_counter()
        if remaining <= 0:
          # window closed: still DRAIN whatever already queued (free —
          # zero extra wait). Under sustained load the first popped
          # request is often older than the window (it queued while
          # the previous batch computed); without this drain every
          # batch would degenerate to size 1 exactly when batching
          # matters most.
          try:
            r = self._q.get_nowait()
          except queue.Empty:
            break
        else:
          try:
            r = self._q.get(timeout=remaining)
          except queue.Empty:
            break
        batch.append(r)
        fill += r.ids.size
      try:
        self._serve_batch(batch)
      except BaseException as e:  # noqa: BLE001 — futures carry it
        for r in batch:
          if not r.future.done():
            r.future.set_exception(e)
          spans.end(r.span, error=f'{type(e).__name__}: {e}')

  def _refresh_stale(self, flat: np.ndarray):
    if self._refresh_fn is None:
      return
    with self._stale_lock:
      if not self._stale:
        return
      stale_now = sorted(set(int(i) for i in flat) & self._stale)
      if not stale_now:
        return
      # claimed under the lock: concurrent batches each refresh a node
      # at most once
      self._stale.difference_update(stale_now)
    try:
      ids = np.asarray(stale_now, np.int64)
      rows = self._refresh_fn(ids)
      self.store.update_rows(ids, rows)
    except BaseException:
      # a failed refresh must NOT un-mark the nodes: the caller's retry
      # would otherwise be served the old stale rows as if fresh
      with self._stale_lock:
        self._stale.update(stale_now)
      raise
    metrics.inc('serving.refreshed', len(stale_now))

  def _serve_batch(self, batch):
    # drop requests already failed elsewhere (a submit that lost the
    # stop() race leaves its request enqueued; replaying it after a
    # restart would dispatch compute and over-count serving.requests
    # for a request nobody is waiting on)
    batch = [r for r in batch if not r.future.done()]
    if not batch:
      return
    t_batch = time.perf_counter()
    t_batch_unix = time.time()
    for r in batch:
      wait = t_batch - r.t0
      metrics.observe('serving.queue_wait_ms', wait * 1e3)
      # retroactive queue span: measured as plain timestamps at pickup
      spans.emit('serving.queue', trace=r.span.trace,
                 parent=r.span.span_id, t0_unix=t_batch_unix - wait,
                 dur_ms=wait * 1e3)
    # one batch span per admission batch. A batch is many-to-one with
    # requests, so it parents under the FIRST request's span (reachable
    # from that request's tree); the other requests link to it via the
    # batch attr stamped on their request spans at respond time.
    flat = np.concatenate([r.ids for r in batch])
    batch_span = spans.begin('serving.batch', attach=False,
                             trace=batch[0].span.trace,
                             parent=batch[0].span.span_id,
                             requests=len(batch))
    try:
      self._refresh_stale(flat)
      outs = []
      pos = 0
      while pos < flat.size:
        take = min(flat.size - pos, self.buckets[-1])
        cap = self._bucket_for(take)
        padded = np.full((cap,), -1, np.int32)
        padded[:take] = flat[pos:pos + take]
        mask = padded >= 0
        metrics.observe('serving.batch_fill', take / cap)
        rows = self.store.fetch(self.store.lookup(padded, mask))
        outs.append(rows[:take])
        metrics.inc('serving.batches')
        pos += take
      rows_all = outs[0] if len(outs) == 1 else np.concatenate(outs)
      compute_s = time.perf_counter() - t_batch
      metrics.observe('serving.compute_ms', compute_s * 1e3)
      spans.emit('serving.compute', trace=batch_span.trace,
                 parent=batch_span.span_id, t0_unix=t_batch_unix,
                 dur_ms=compute_s * 1e3, ids=int(flat.size))
    finally:
      # a raising refresh/fetch must not strand the batch span open —
      # it would simply never be emitted (attach=False), hiding the
      # failed batch from the trace it belongs to
      spans.end(batch_span, fill=int(flat.size))
    o = 0
    for r in batch:
      res = rows_all[o:o + r.ids.size]
      o += r.ids.size
      # metrics BEFORE set_result: a caller reading counters right
      # after .result() returns must see its own request counted
      metrics.inc('serving.requests')
      metrics.observe('serving.total_ms',
                      (time.perf_counter() - r.t0) * 1e3)
      t_resp = time.perf_counter()
      if not r.future.done():   # lost a stop() race: already failed
        r.future.set_result(res)
      spans.emit('serving.respond', trace=r.span.trace,
                 parent=r.span.span_id,
                 dur_ms=(time.perf_counter() - t_resp) * 1e3)
      # close the request span: its duration IS the request's
      # enqueue->rows latency (span-derived p50/p99 agrees with the
      # serving.total_ms histogram — tested within one bucket ratio)
      spans.end(r.span, batch=batch_span.span_id)

  # ------------------------------------------------------------- remote

  def serve_numpy(self, ids) -> np.ndarray:
    """Synchronous host entry for the ``serve`` RPC
    (DistServer.serve): submit through the same admission queue so
    remote traffic batches with local traffic, block for the rows.
    Read-only w.r.t. the caller — idempotent by construction, retried
    safely under the fault registry."""
    return self.lookup(np.asarray(ids, np.int64))
