"""Sampling producers: collocated (in-process) and mp (subprocess) batch
production into a channel.

TPU-native port of
/root/reference/graphlearn_torch/python/distributed/dist_sampling_producer.py.
The mp producer spawns worker subprocesses that run the sampler over a
static split of the seed range and push serialized SampleMessages into the
shared shm channel (reference _sampling_worker_loop, :53-151). Worker
subprocesses force the CPU jax backend — the TPU chips belong to the
training process (single-controller model), so host-side producers sample
on CPU; the fast path for device sampling is the collocated mesh program.
"""
import multiprocessing as mp
import threading
from enum import Enum
from typing import Optional

import numpy as np

from ..channel import ChannelBase
from ..sampler import NodeSamplerInput, SamplingConfig, SamplingType
from .message import hetero_output_to_message, output_to_message


class MpCommand(Enum):
  """Reference: dist_sampling_producer.py MpCommand."""
  SAMPLE_ALL = 0
  STOP = 1


def _sampling_worker_loop(rank, dataset_handle, sampling_config, seeds,
                          task_queue, channel, done_counter,
                          progress=None, resume_calls: int = 0,
                          metrics_q=None):
  """Subprocess body (reference: dist_sampling_producer.py:53-151).

  Self-healing contract: after every batch lands in the channel the
  worker publishes (batches sent this epoch, sampler call_count) into
  the shared ``progress`` arrays. A crashed worker is respawned with
  ``resume_calls`` = its last published call_count and replays its
  epoch order from the first unsent batch — the sampler's fold_in
  per-call key stream makes the replayed batches bit-identical to what
  the dead worker would have produced (batch i's key depends only on
  (worker seed, call index), never on history).
  """
  # one process per chip: the parent holds it, so this worker selects
  # the CPU backend before its first backend use (a spawned child has
  # initialised none; a failure here must be loud, never a TPU open)
  import jax
  jax.config.update('jax_platforms', 'cpu')
  import graphlearn_tpu as glt

  # rebuild from host-side ipc handles; device state stays on CPU here
  gipc = dataset_handle['graph_ipc']
  hetero = isinstance(gipc, dict)
  if hetero:
    graph = {tuple(et): glt.data.Graph(h[0], 'CPU')
             for et, h in gipc.items()}
  else:
    topo, _ = gipc
    graph = glt.data.Graph(topo, 'CPU')
  fipc = dataset_handle['feature_ipc']
  feature = None
  if fipc is not None:
    def _rebuild(h):
      f = glt.data.Feature.from_ipc_handle(h)
      f.with_device = False
      return f
    feature = ({t: _rebuild(h) for t, h in fipc.items()}
               if isinstance(fipc, dict) else _rebuild(fipc))
  dataset = glt.data.Dataset(graph, feature, None,
                             dataset_handle['node_labels'],
                             dataset_handle['edge_dir'])
  input_type = dataset_handle.get('input_type')
  cfg: SamplingConfig = sampling_config
  # fold the worker rank into the seed: same-seeded workers would draw
  # IDENTICAL negative edges per batch index (negatives depend only on
  # the graph + key, not the positives), collapsing negative diversity
  worker_seed = (0 if cfg.seed is None else cfg.seed) * 1000003 + rank
  sampler = glt.sampler.NeighborSampler(
      dataset.graph, cfg.num_neighbors, with_edge=cfg.with_edge,
      with_weight=cfg.with_weight, edge_dir=cfg.edge_dir,
      seed=worker_seed)
  # restart path: fast-forward the PRNG stream to where the dead worker
  # left it, so replayed batches reuse the exact per-call keys
  if resume_calls:
    sampler._call_count = resume_calls
  from graphlearn_tpu.sampler import (EdgeSamplerInput, NegativeSampling,
                                      SamplingType)
  is_link = cfg.sampling_type == SamplingType.LINK
  if is_link:
    # seeds is a dict payload for link sampling (reference producers
    # branch on the config's sampling type the same way,
    # dist_sampling_producer.py:106-140)
    rows_, cols_ = seeds['rows'], seeds['cols']
    label_ = seeds.get('label')
    neg = (NegativeSampling(seeds['neg_mode'], seeds['neg_amount'])
           if seeds.get('neg_mode') else None)
    n_seeds = rows_.shape[0]
  else:
    n_seeds = seeds.shape[0]
  from graphlearn_tpu import metrics
  from graphlearn_tpu.utils.faults import fault_point
  import os as _os
  import queue as _queue
  import time as _time
  parent = _os.getppid()
  while True:
    try:
      cmd, payload = task_queue.get(timeout=5)
    except _queue.Empty:
      # orphan guard: a SIGKILL'd producer process cannot STOP its
      # workers; when the parent is gone (reparented to init) exit
      # instead of idling forever as a leaked process
      if _os.getppid() != parent:
        return
      continue
    if cmd == MpCommand.STOP:
      break
    # payload: (epoch order, replay start batch, span wire-context) —
    # the ctx joins this worker's spans to the driving client's trace
    # (a replayed command after a respawn carries the SAME ctx, so the
    # replacement incarnation's spans land in the same tree, orphan-
    # free). Two-tuple payloads from older callers still work.
    epoch_seed_order, start_batch = payload[0], payload[1]
    span_ctx = payload[2] if len(payload) > 2 else None
    from graphlearn_tpu.metrics import spans
    n = n_seeds
    bs = cfg.batch_size
    batch_no = 0
    epoch_ctx = spans.adopt(span_ctx)
    epoch_ctx.__enter__()
    epoch_span = spans.begin('producer.epoch', worker=rank,
                             start_batch=start_batch)
    try:
      for i in range(0, n - (n % bs if cfg.drop_last else 0), bs):
        idx = epoch_seed_order[i:i + bs]
        if idx.shape[0] == 0:
          continue
        if batch_no < start_batch:
          # replay fast-forward: these batches already landed in the
          # channel before the previous incarnation died; the PRNG keys
          # they consumed are covered by resume_calls, so skipping them
          # does not shift the remaining batches' key stream
          batch_no += 1
          continue
        # chaos harness site: armed 'exit' here (before the sample/send)
        # kills the worker at an exact batch index with nothing in flight
        fault_point('producer.worker.batch')
        batch_span = spans.begin('producer.batch', batch=batch_no)
        try:
          t_batch = _time.perf_counter()
          if is_link:
            if idx.shape[0] < bs:
              # pad the final short batch cyclically so every batch keeps
              # the compiled shape (a fresh length would retrace the whole
              # chain per epoch); the few duplicated positives are slightly
              # over-weighted in that one batch
              idx = np.resize(idx, bs)
            out = sampler.sample_from_edges(EdgeSamplerInput(
                rows_[idx], cols_[idx],
                label=(label_[idx] if label_ is not None else None),
                input_type=input_type,
                neg_sampling=neg))
          else:
            out = sampler.sample_from_nodes(
                NodeSamplerInput(seeds[idx], input_type=input_type),
                batch_cap=bs)
          if hetero:
            x_d = y_d = None
            if cfg.collect_features and \
                isinstance(dataset.node_features, dict):
              x_d = {t: dataset.node_features[t].cpu_get(
                  np.maximum(np.asarray(out.node[t]), 0))
                  for t in out.node if t in dataset.node_features}
            if isinstance(dataset.node_labels, dict):
              y_d = {}
              for t, lab in dataset.node_labels.items():
                if t not in out.node:
                  continue
                lab = np.asarray(lab)
                y_d[t] = lab[np.clip(np.asarray(out.node[t]), 0,
                                     len(lab) - 1)]
            msg = hetero_output_to_message(out, x_d, y_d)
          else:
            x = y = None
            if cfg.collect_features and dataset.node_features is not None:
              x = dataset.node_features.cpu_get(
                  np.maximum(np.asarray(out.node), 0))
            if dataset.node_labels is not None:
              labels = np.asarray(dataset.node_labels)
              y = labels[np.clip(np.asarray(out.node), 0,
                                 len(labels) - 1)]
            msg = output_to_message(out, x, y)
          channel.send(msg)
          # worker-local observability: this subprocess's own registry; it
          # reaches the trainer through the metrics_q snapshot below (and
          # DistServer.get_metrics / metrics.scrape_all from there)
          metrics.inc('producer.batches')
          metrics.observe('producer.sample_ms',
                          (_time.perf_counter() - t_batch) * 1e3)
        finally:
          # a raising sample/send must not strand the batch span on this
          # worker's context stack — later batches would parent under it
          spans.end(batch_span)
        batch_no += 1
        if progress is not None:
          # published AFTER the send. Tradeoff for an UNCONTROLLED crash
          # landing exactly between send and publish: the replay re-emits
          # that one batch (a duplicate, which consumers counting toward
          # expected will take in place of the true final batch) —
          # publishing first would instead lose the batch outright.
          # Exact replay is guaranteed when the crash point is before the
          # send, which is where the chaos harness injects kills
          # (docs/failure_model.md 'Limits').
          sent_arr, calls_arr = progress
          with sent_arr.get_lock():
            sent_arr[rank] = batch_no
            calls_arr[rank] = sampler._call_count
    finally:
      # the epoch span and adopted trace context close even when a
      # batch raises out of the loop — the respawned incarnation's
      # replay re-adopts the same ctx and must not nest under a stale
      # leaked span
      spans.end(epoch_span, batches=batch_no)
      epoch_ctx.__exit__(None, None, None)
    with done_counter.get_lock():
      done_counter.value += 1
    if metrics_q is not None:
      # publish the CUMULATIVE worker snapshot at epoch end over the
      # producer's queue plumbing — latest-wins per rank on the other
      # side, so a lost/duplicated frame costs nothing. The snapshot
      # carries this worker's span ring + the epoch's trace id as
      # extra keys: DistServer.get_metrics (and worker_metrics) expose
      # them so a scrape recovers producer spans by id alone
      try:
        snap = metrics.snapshot()
        snap['spans'] = spans.export(limit=spans.SCRAPE_EXPORT_LIMIT)
        snap['run_id'] = (span_ctx or {}).get('trace') or spans.run_id()
        metrics_q.put_nowait((rank, snap))
      except Exception:  # noqa: BLE001 - observability must not kill work
        pass


class DistMpSamplingProducer:
  """Spawn N sampling subprocesses feeding `channel`
  (reference: dist_sampling_producer.py:154-280)."""

  def __init__(self, dataset, sampler_input,
               sampling_config: SamplingConfig, channel: ChannelBase,
               num_workers: int = 1, seed: Optional[int] = None,
               max_worker_restarts: int = 2):
    self.dataset = dataset
    self.config = sampling_config
    # self-healing budget: check_worker_health respawns a crashed worker
    # (replaying its unfinished seed blocks bit-identically) at most
    # this many times per producer before giving up
    self.max_worker_restarts = max_worker_restarts
    self._restarts_used = 0
    # serializes crash detection + respawn: the server calls
    # check_worker_health from concurrent RPC handler threads (one per
    # puller connection), and a double-respawn of the same worker would
    # replay its seed tail twice
    self._health_lock = threading.Lock()
    if hasattr(sampler_input, 'row'):     # EdgeSamplerInput (link mode)
      neg = sampler_input.neg_sampling
      self._link_input = dict(
          rows=np.asarray(sampler_input.row).reshape(-1),
          cols=np.asarray(sampler_input.col).reshape(-1),
          label=(np.asarray(sampler_input.label).reshape(-1)
                 if sampler_input.label is not None else None),
          neg_mode=(neg.mode if neg is not None else None),
          neg_amount=(neg.amount if neg is not None else 1))
      # one channel for the typed-seed tag: the shared dataset handle
      # (input_type below), not per-worker seed payloads
      self._input_type = getattr(sampler_input, 'input_type', None)
      n = self._link_input['rows'].shape[0]
      self.seeds = None
    else:
      self._link_input = None
      self.seeds = np.asarray(sampler_input.node).reshape(-1)
      self._input_type = getattr(sampler_input, 'input_type', None)
      n = self.seeds.shape[0]
    # typed-graph contract, validated HERE so every mp consumer (node
    # loader, link loader, server producers) fails fast instead of a
    # worker assert surfacing as a 60s channel timeout
    if isinstance(dataset.graph, dict) and self._input_type is None:
      raise ValueError(
          'hetero sampling requires typed seeds — pass '
          "('ntype', ids) node seeds (or a NodeSamplerInput with "
          'input_type), or ((src, rel, dst), edge_label_index) link '
          'seeds (EdgeSamplerInput with input_type)')
    self._num_seeds = n
    self.channel = channel
    self.num_workers = num_workers
    self._rng = np.random.default_rng(seed)
    self._procs = []
    self._queues = []
    self._done = None
    self._splits = np.array_split(np.arange(n), num_workers)

  def _worker_seeds(self, w: int):
    if self._link_input is not None:
      sl = self._splits[w]
      li = self._link_input
      return dict(rows=li['rows'][sl], cols=li['cols'][sl],
                  label=(li['label'][sl] if li['label'] is not None
                         else None),
                  neg_mode=li['neg_mode'],
                  neg_amount=li['neg_amount'])
    return self.seeds[self._splits[w]]

  def _spawn_worker(self, w: int, resume_calls: int = 0):
    q = self._ctx.Queue()
    p = self._ctx.Process(
        target=_sampling_worker_loop,
        args=(w, self._handle, self.config, self._worker_seeds(w), q,
              self.channel, self._done, (self._sent, self._calls),
              resume_calls, self._metrics_q),
        daemon=True)
    p.start()
    self._procs[w] = p
    self._queues[w] = q

  def init(self):
    ctx = self._ctx = mp.get_context('spawn')
    self._done = ctx.Value('i', 0)
    # per-worker progress, shared with the subprocesses: batches sent in
    # the current epoch + the sampler's call_count — everything the
    # restart path needs to replay a dead worker exactly
    self._sent = ctx.Array('q', self.num_workers)
    self._calls = ctx.Array('q', self.num_workers)
    # worker metric snapshots ride their own small queue (epoch-end
    # cadence, latest-wins) — NEVER the data channel, whose message
    # count is the epoch-completion contract
    self._metrics_q = ctx.Queue()
    self._worker_snaps = {}
    self._metrics_drain_lock = threading.Lock()
    self._last_orders = [None] * self.num_workers
    self._last_ctx = [None] * self.num_workers
    g = self.dataset.graph
    nf = self.dataset.node_features
    self._handle = dict(
        graph_ipc=({et: gr.share_ipc() for et, gr in g.items()}
                   if isinstance(g, dict) else g.share_ipc()),
        feature_ipc=(None if nf is None else
                     {t: f.share_ipc() for t, f in nf.items()}
                     if isinstance(nf, dict) else nf.share_ipc()),
        node_labels=self.dataset.node_labels,
        edge_dir=self.dataset.edge_dir,
        input_type=getattr(self, '_input_type', None))
    # ship host containers; subprocesses rebuild on the CPU backend
    self._procs = [None] * self.num_workers
    self._queues = [None] * self.num_workers
    for w in range(self.num_workers):
      self._spawn_worker(w)

  def produce_all(self):
    """Kick one epoch of sampling on all workers
    (reference: :227-240)."""
    from ..metrics import spans
    with self._done.get_lock():
      self._done.value = 0
    with self._sent.get_lock():
      for w in range(self.num_workers):
        self._sent[w] = 0
    if hasattr(self.channel, 'reset'):
      self.channel.reset()
    # the epoch command carries the CALLER's span context (the client's
    # epoch span when produce_all was reached through an RPC whose
    # handler adopted it) so worker spans join the driving trace; kept
    # per worker for replay — a respawned incarnation must land its
    # spans in the SAME tree
    ctx = spans.wire_context()
    for w in range(self.num_workers):
      n = self._splits[w].shape[0]
      order = (self._rng.permutation(n) if self.config.shuffle
               else np.arange(n))
      self._last_orders[w] = order
      self._last_ctx[w] = ctx
      self._queues[w].put((MpCommand.SAMPLE_ALL, (order, 0, ctx)))

  def is_all_sampling_completed(self) -> bool:
    with self._done.get_lock():
      return self._done.value == self.num_workers

  def _expected_for_worker(self, w: int) -> int:
    n = self._splits[w].shape[0]
    bs = self.config.batch_size
    return n // bs if self.config.drop_last else -(-n // bs)

  def check_worker_health(self):
    """Detect crashed sampling subprocesses and self-heal.

    A worker with a nonzero exit code is respawned with the sampler
    PRNG stream fast-forwarded to its last published call_count, and
    its current epoch order is replayed from the first unsent batch —
    bit-identical to what the dead worker would have produced (see
    _sampling_worker_loop). After ``max_worker_restarts`` respawns the
    producer gives up and raises, so a deterministically-crashing
    worker cannot restart-loop forever. Thread-safe: concurrent callers
    (the server's per-connection RPC threads) serialize on a lock, and
    the post-lock re-read of self._procs sees a sibling's respawn as a
    healthy worker instead of restarting it twice.
    """
    with self._health_lock:
      self._check_worker_health_locked()

  def _check_worker_health_locked(self):
    for w in range(len(self._procs)):
      p = self._procs[w]
      if p is None or p.exitcode is None or p.exitcode == 0:
        continue
      if self._restarts_used >= self.max_worker_restarts:
        raise RuntimeError(
            f'sampling worker {w} (pid={p.pid}) died with exit code '
            f'{p.exitcode} and the restart budget '
            f'({self.max_worker_restarts}) is exhausted — giving up')
      self._restarts_used += 1
      with self._sent.get_lock():
        sent = int(self._sent[w])
        calls = int(self._calls[w])
      from ..utils import trace
      trace.counter_inc('resilience.worker_restart')
      import logging
      logging.getLogger('graphlearn_tpu.producer').warning(
          'sampling worker %d (pid=%s) died with exit code %s after %d '
          'batches; respawning (restart %d/%d) and replaying from batch '
          '%d', w, p.pid, p.exitcode, sent, self._restarts_used,
          self.max_worker_restarts, sent)
      self._spawn_worker(w, resume_calls=calls)
      order = self._last_orders[w]
      if order is not None and sent < self._expected_for_worker(w):
        # mid-epoch death: replay the unfinished tail of its seed order
        # under the SAME span context — the respawned incarnation's
        # spans join the original epoch's tree (no orphans)
        self._queues[w].put((MpCommand.SAMPLE_ALL,
                             (order, sent, self._last_ctx[w])))

  def worker_metrics(self):
    """Merged metric snapshot across this producer's mp workers, or
    None before any worker has published (workers push cumulative
    snapshots at epoch end over ``_metrics_q``; latest-wins per rank —
    a respawned worker's fresh registry simply restarts its series).
    The drain is serialized under a lock: concurrent callers (the
    owning loader + DistServer.get_metrics RPC-handler threads) racing
    get_nowait against the per-rank dict write could otherwise land an
    OLDER frame over a newer one and make the cumulative series step
    backwards until the next epoch-end publish."""
    import queue as _queue
    q = getattr(self, '_metrics_q', None)
    if q is None:
      return None
    with self._metrics_drain_lock:
      while True:
        try:
          rank, snap = q.get_nowait()
        except (_queue.Empty, OSError, ValueError):
          break
        self._worker_snaps[rank] = snap
      if not self._worker_snaps:
        return None
      snaps = list(self._worker_snaps.values())
    from ..metrics import merge_snapshots
    merged = merge_snapshots(snaps)
    # span rings don't merge — concatenate them (and carry a run_id)
    # so get_metrics / scrape_all expose producer spans per role
    span_rows = [s for snap in snaps for s in snap.get('spans', ())]
    if span_rows:
      merged['spans'] = span_rows
    for snap in snaps:
      if snap.get('run_id'):
        merged['run_id'] = snap['run_id']
        break
    return merged

  def num_expected(self) -> int:
    bs = self.config.batch_size
    total = 0
    for s in self._splits:
      n = s.shape[0]
      total += n // bs if self.config.drop_last else -(-n // bs)
    return total

  def shutdown(self):
    """Idempotent: a second shutdown (epoch teardown racing server exit)
    is a no-op."""
    if getattr(self, '_shutdown_done', False):
      return
    self._shutdown_done = True
    for q in self._queues:
      try:
        q.put((MpCommand.STOP, None))
      except Exception:
        pass
    for p in self._procs:
      if p is None:
        continue
      p.join(timeout=5)
      if p.is_alive():
        import logging
        logging.getLogger('graphlearn_tpu.producer').warning(
            'sampling worker %s did not exit within 5s; terminating',
            p.pid)
        p.terminate()


class DistCollocatedSamplingProducer:
  """In-process synchronous producer (reference: :283-349)."""

  def __init__(self, dataset, sampler_input: NodeSamplerInput,
               sampling_config: SamplingConfig,
               seed: Optional[int] = None):
    import graphlearn_tpu as glt
    self.dataset = dataset
    self.seeds = np.asarray(sampler_input.node).reshape(-1)
    self.config = sampling_config
    cfg = sampling_config
    self.sampler = glt.sampler.NeighborSampler(
        dataset.graph, cfg.num_neighbors, with_edge=cfg.with_edge,
        with_weight=cfg.with_weight, edge_dir=cfg.edge_dir, seed=cfg.seed)
    self._rng = np.random.default_rng(seed)
    self._order = None
    self._pos = 0

  def reset(self):
    self._order = (self._rng.permutation(self.seeds.shape[0])
                   if self.config.shuffle
                   else np.arange(self.seeds.shape[0]))
    self._pos = 0

  def sample(self):
    """Produce the next batch's message, or None at epoch end."""
    if self._order is None:
      self.reset()
    bs = self.config.batch_size
    n = self.seeds.shape[0]
    if self._pos >= n or (self.config.drop_last and
                          self._pos + bs > n):
      return None
    idx = self._order[self._pos:self._pos + bs]
    self._pos += bs
    out = self.sampler.sample_from_nodes(NodeSamplerInput(self.seeds[idx]),
                                         batch_cap=bs)
    x = y = None
    if self.config.collect_features and \
        self.dataset.node_features is not None:
      x = self.dataset.node_features.cpu_get(
          np.maximum(np.asarray(out.node), 0))
    if self.dataset.node_labels is not None:
      labels = np.asarray(self.dataset.node_labels)
      y = labels[np.clip(np.asarray(out.node), 0, len(labels) - 1)]
    return output_to_message(out, x, y)
