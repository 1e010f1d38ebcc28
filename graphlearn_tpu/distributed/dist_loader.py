"""Distributed loaders: per-shard batches over the mesh.

TPU-native re-design of
/root/reference/graphlearn_torch/python/distributed/dist_loader.py +
dist_neighbor_loader.py. The reference dispatches between collocated /
multiprocess / remote sampling workers feeding a channel; on TPU the
sampling step IS a compiled SPMD program on the same mesh as training, so
the default loader is the collocated equivalent: every iteration draws
P seed blocks (one per shard), runs the jitted distributed sample, and
yields a stacked `Data` whose leading axis is the partition ('g'/data)
axis. Mp/remote modes (host-process producers + channels) live in
dist_server/dist_client.
"""
from typing import List, Optional

import numpy as np

from ..loader import Data
from ..loader.node_loader import OverflowGuardMixin
from ..sampler import NodeSamplerInput
from .dist_dataset import DistDataset
from .dist_neighbor_sampler import DistNeighborSampler
from .tenancy import with_backpressure


def _split_input_type(input_nodes):
  """The framework-wide seed convention: ``('ntype', ids)`` for typed
  seeds, a bare array otherwise. ONE implementation for every loader
  front-end (collocated / mp / remote)."""
  if isinstance(input_nodes, tuple) and len(input_nodes) == 2 and \
      isinstance(input_nodes[0], str):
    return input_nodes[0], input_nodes[1]
  return None, input_nodes


def _norm_num_neighbors(num_neighbors):
  """Picklable copy: per-etype dict fanouts or a shared list."""
  return (dict(num_neighbors) if isinstance(num_neighbors, dict)
          else list(num_neighbors))


from ..typing import split_edge_type_seeds as _split_edge_type  # noqa: E402


class DistLoader(OverflowGuardMixin):
  """Reference: dist_loader.py:128-441 (collocated branch)."""

  def __init__(self, data: DistDataset, sampler: DistNeighborSampler,
               input_nodes, batch_size: int = 64, shuffle: bool = False,
               drop_last: bool = True, collect_features: bool = True,
               seed: Optional[int] = None,
               seed_labels_only: bool = False,
               overflow_policy: str = 'raise'):
    self.data = data
    self.sampler = sampler
    self._init_overflow_policy(overflow_policy)
    # seed_labels_only: gather y for the per-shard seed block only
    # (supervision reads seed slots; skips a full-capacity sharded
    # label gather — the same knob as the local loaders)
    self.seed_labels_only = seed_labels_only
    self.input_type, input_nodes = _split_input_type(input_nodes)
    self.input_seeds = np.asarray(input_nodes).reshape(-1)
    self.batch_size = batch_size  # per shard
    self.shuffle = shuffle
    self.drop_last = drop_last
    self.collect_features = collect_features
    self.seed = seed   # kept: DistScanTrainer derives its perm key here
    self._rng = np.random.default_rng(seed)
    self.num_partitions = data.num_partitions
    self._flight_epochs = 0   # epochs RECORDED (metrics/flight.py)

  def __len__(self):
    g = self.num_partitions * self.batch_size
    n = self._num_seeds()
    return n // g if self.drop_last else (n + g - 1) // g

  def _num_seeds(self):
    return self.input_seeds.shape[0]

  def state_dict(self):
    """Resumable iteration state (epoch-boundary granularity): the seed
    shuffle stream + the SPMD sampler's PRNG state (delegated)."""
    return {'rng_state': self._rng.bit_generator.state,
            'sampler': self.sampler.state_dict()}

  def load_state_dict(self, state):
    self._rng.bit_generator.state = state['rng_state']
    if 'sampler' in state:
      self.sampler.load_state_dict(state['sampler'])

  def _index_blocks(self):
    """Yield ([P, B] seed-index blocks, validity mask or None) per step.

    The final short block is padded by repeating indices (cyclically, so
    it works even with fewer total seeds than one global batch) but
    carries a validity mask: pad seeds produce no nodes/edges in the
    sampler and consumers can exclude them (no silent double-counting;
    the reference emits a short batch instead, dist_loader.py:284-295).
    """
    n = self._num_seeds()
    order = self._rng.permutation(n) if self.shuffle else np.arange(n)
    g = self.num_partitions * self.batch_size
    shape = (self.num_partitions, self.batch_size)
    for s in range(len(self)):
      idx = order[s * g:(s + 1) * g]
      n_valid = idx.shape[0]
      mask = None
      if n_valid < g:
        idx = np.concatenate([idx, np.resize(order, g - n_valid)])
        mask = (np.arange(g) < n_valid).reshape(shape)
      yield idx.reshape(shape), mask

  # -- epoch flight records (metrics/flight.py; docs/observability.md):
  # every per-step loader epoch appends ONE JSONL record to GLT_RUN_LOG
  # — steps yielded, wall, dispatch/feature/resilience counter deltas.
  # Pure host bookkeeping around the existing loop (the feature fields
  # come from the publish_stats fetch the epoch already pays).

  def _flight_begin(self):
    from ..metrics import flight, spans
    # one epoch.run span per epoch alongside the flight record: both
    # carry the process run_id, so a flight line, a scrape and the
    # epoch's span tree join on one id (docs/observability.md)
    return (flight.epoch_begin(),
            spans.begin('epoch.run', emitter=type(self).__name__))

  def _flight_end(self, tok, steps: int, completed: bool):
    from ..metrics import flight, spans
    flight_tok, span_tok = tok
    spans.end(span_tok, steps=steps, completed=completed)
    flight.end_for(self, flight_tok, steps=steps, completed=completed,
                   config=self._flight_config())

  def _flight_config(self) -> dict:
    """Static epoch configuration (fingerprinted in flight records)."""
    return dict(loader=type(self).__name__, batch_size=self.batch_size,
                shuffle=self.shuffle, drop_last=self.drop_last,
                num_partitions=self.num_partitions, seed=self.seed,
                num_neighbors=getattr(self.sampler, 'num_neighbors',
                                      None))

  def __iter__(self):
    from ..metrics import spans
    # overflow-policy state resolves BEFORE the span/flight bracket: a
    # raise from it must not leak the attached epoch.run span (which
    # would mis-parent every later span on this thread)
    guarded, recompute = self._overflow_epoch_start()
    tok = self._flight_begin()
    steps, completed = 0, False
    try:
      for i, (idx, mask) in enumerate(self._index_blocks()):
        # closed before the yield: see NodeLoader.__iter__
        with spans.span('loader.batch', step=i):
          inp = NodeSamplerInput(self.input_seeds[idx], self.input_type)
          if recompute:
            keys = self.sampler._next_keys()
            out = self.sampler.sample_from_nodes(inp, seed_mask=mask,
                                                 keys=keys)
            if self._batch_overflowed(out):
              self.overflow_recomputes += 1
              out = self._replay_sampler().sample_from_nodes(
                  inp, seed_mask=mask, keys=keys)
          else:
            out = self.sampler.sample_from_nodes(inp, seed_mask=mask)
            if guarded:
              self._accumulate_overflow(out)
          batch = self._collate_fn(out)
        yield batch
        steps += 1
      completed = True
      if guarded and not recompute:
        self._finish_epoch_overflow()
    finally:
      # also on early break/close: the on-device int32 accumulator must
      # be drained per epoch or it eventually wraps. The publish is a
      # device fetch that can raise — the span/flight close must
      # survive it (inner finally), or the attached epoch span leaks
      try:
        self._publish_feature_stats()
      finally:
        self._flight_end(tok, steps, completed)

  def _publish_feature_stats(self):
    """Surface the feature-store hit/miss counters into utils.trace at
    EPOCH granularity — the counters accumulate on device across the
    epoch's batches (DistFeature threads them through its one dispatch),
    so this is the only device->host stats fetch of the feature path.
    Edge-feature stores publish too: their accumulators thread through
    every edge_attr gather and must be drained each epoch (an unread
    int32 accumulator would eventually wrap). The sampler's sharded
    LABEL stores are DistFeatures with the same accumulator and the
    same wrap hazard — they drain here too, under 'dist_label' so the
    headline dist_feature.* parity (per-step vs scanned, which skips
    label-stat accumulation by design) is untouched."""
    for f in self.data.feature_stores():
      f.publish_stats()
    for f in self.sampler.label_stores():
      f.publish_stats(prefix='dist_label')

  def _collate_fn(self, out):
    """SamplerOutput [P, ...] -> stacked Data/HeteroData (reference:
    dist_loader.py:331-441 parses the channel SampleMessage; here arrays
    are already device-resident and sharded)."""
    from .. import ops
    from ..utils.trace import record_dispatch
    # the collate's own program launches (edge_index stack; the feature
    # and label gathers count separately under 'dist_feature.get') —
    # together with 'dist_sample' this makes the per-step distributed
    # loop's >= 2 dispatches/step an assertable budget, not arithmetic
    record_dispatch('dist_collate')
    from ..loader import HeteroData
    from ..sampler import HeteroSamplerOutput
    x, y = self.sampler.collate(
        out, self.data.node_labels,
        label_cap=(self.batch_size if self.seed_labels_only else None))
    if isinstance(out, HeteroSamplerOutput):
      ei = {et: ops.stack2_batched(out.row[et], out.col[et])
            for et in out.row}
      edge_attr = None
      efs = getattr(self.data, 'edge_features', None)
      if out.edge is not None and efs:
        # batches key edges by the message-direction (reversed) type; the
        # ids belong to the ORIGINAL edge type's id space
        from ..typing import reverse_edge_type
        edge_attr = {}
        for et in out.edge:
          src_et = (reverse_edge_type(et) if self.data.edge_dir == 'out'
                    else et)
          if src_et in efs:
            edge_attr[et] = efs[src_et].get(out.edge[et])
        edge_attr = edge_attr or None
      return HeteroData(node=out.node, num_nodes=out.num_nodes,
                        edge_index=ei, edge_mask=out.edge_mask, x=x, y=y,
                        edge_ids=out.edge, edge_attr=edge_attr,
                        batch=out.batch,
                        batch_size=out.batch_size,
                        num_sampled_nodes=out.num_sampled_nodes,
                        num_sampled_edges=out.num_sampled_edges,
                        metadata=dict(out.metadata))
    edge_attr = None
    if out.edge is not None and \
        getattr(self.data, 'edge_features', None) is not None:
      edge_attr = self.data.edge_features.get(out.edge)
    ei = ops.stack2_batched(out.row, out.col)  # [P, 2, E]
    return Data(node=out.node, num_nodes=out.num_nodes,
                edge_index=ei, edge_mask=out.edge_mask, x=x, y=y,
                edge_ids=out.edge, edge_attr=edge_attr, batch=out.batch,
                batch_size=out.batch_size,
                num_sampled_nodes=out.num_sampled_nodes,
                num_sampled_edges=out.num_sampled_edges,
                metadata=dict(out.metadata))


class MpDistNeighborLoader:
  """Mp worker mode: sampling subprocesses feed a native shm channel, the
  loader drains it (reference: dist_loader.py:226-302 mp branch). Use when
  host-side seed prep/feature IO should overlap device training; the
  collocated mesh loader (DistNeighborLoader) is the device-fast path."""

  def __init__(self, data, num_neighbors, input_nodes,
               batch_size: int = 64, shuffle: bool = False,
               drop_last: bool = False, with_edge: bool = False,
               collect_features: bool = True, num_workers: int = 2,
               channel_size: int = 1 << 26, seed: Optional[int] = None,
               max_worker_restarts: int = 2):
    from ..sampler import SamplingConfig, SamplingType
    # hetero seeds: ('paper', ids) — workers sample the typed engine and
    # stream HeteroData messages (message.hetero_output_to_message)
    input_type, input_nodes = _split_input_type(input_nodes)
    config = SamplingConfig(
        SamplingType.NODE, _norm_num_neighbors(num_neighbors),
        batch_size, shuffle, drop_last, with_edge, collect_features,
        False, False, data.edge_dir, seed)
    self._setup(data,
                NodeSamplerInput(np.asarray(input_nodes).reshape(-1),
                                 input_type=input_type),
                config, channel_size, num_workers, seed,
                max_worker_restarts=max_worker_restarts)

  def _setup(self, data, sampler_input, config, channel_size, num_workers,
             seed, max_worker_restarts: int = 2):
    """Shared producer/channel wiring for the mp loader family."""
    from ..channel import QueueTimeoutError, ShmChannel
    from .dist_sampling_producer import DistMpSamplingProducer
    from .message import message_to_data
    self._message_to_data = message_to_data
    self._timeout_error = QueueTimeoutError
    self.channel = ShmChannel(shm_size=channel_size)
    self.producer = DistMpSamplingProducer(
        data, sampler_input, config, self.channel,
        num_workers=num_workers, seed=seed,
        max_worker_restarts=max_worker_restarts)
    self.producer.init()
    self._expected = self.producer.num_expected()
    # recv window between producer health checks: short enough that a
    # crashed worker is detected (and restarted) promptly, long enough
    # that the checks stay off the hot path
    self.health_check_interval_ms = 5000

  def __len__(self):
    return self._expected

  def __iter__(self):
    from ..metrics import flight, spans
    cfg = self.producer.config
    tok = flight.epoch_begin()
    # the epoch span is CURRENT while produce_all ships the epoch
    # commands, so worker spans (producer.epoch/batch) parent under it;
    # produce_all runs INSIDE the try — a raise there must still end
    # the attached span (and now also records the failed epoch)
    sp = spans.begin('epoch.run', emitter=type(self).__name__)
    received = 0
    try:
      self.producer.produce_all()
      while received < self._expected:
        try:
          msg = self.channel.recv(
              timeout_ms=self.health_check_interval_ms)
        except self._timeout_error:
          # crashed worker -> restart + bit-identical replay (raises
          # only once the producer's restart budget is exhausted),
          # rather than spinning on an empty channel forever
          self.producer.check_worker_health()
          if self.producer.is_all_sampling_completed() and \
              self.channel.empty():
            break
          continue
        received += 1
        yield self._message_to_data(msg)
    finally:
      spans.end(sp, steps=received,
                completed=received >= self._expected)
      flight.end_for(
          self, tok, steps=received,
          completed=received >= self._expected,
          config=dict(loader=type(self).__name__,
                      batch_size=cfg.batch_size, shuffle=cfg.shuffle,
                      num_neighbors=cfg.num_neighbors,
                      num_workers=self.producer.num_workers))

  def worker_metrics(self):
    """Merged metric snapshot across this loader's mp sampling workers
    (see DistMpSamplingProducer.worker_metrics); None before the first
    epoch-end publish."""
    return self.producer.worker_metrics()

  def shutdown(self):
    self.producer.shutdown()
    self.channel.close()


class MpDistLinkNeighborLoader(MpDistNeighborLoader):
  """Mp worker mode for LINK sampling: subprocesses run
  sample_from_edges (positives + negatives) and stream batches with
  edge_label_index/edge_label metadata over the shm channel (reference:
  the link branch of the sampling producers,
  dist_sampling_producer.py:106-140)."""

  def __init__(self, data, num_neighbors: List[int], edge_label_index,
               edge_label=None, neg_sampling=None, batch_size: int = 64,
               shuffle: bool = False, drop_last: bool = False,
               with_edge: bool = False, collect_features: bool = True,
               num_workers: int = 2, channel_size: int = 1 << 26,
               seed: Optional[int] = None):
    from ..sampler import (EdgeSamplerInput, SamplingConfig, SamplingType)
    # hetero seed edges: ((src_t, rel, dst_t), [2, E]) — the LinkLoader
    # tuple convention; workers run the typed link engine
    edge_type, edge_label_index = _split_edge_type(edge_label_index)
    ei = np.asarray(edge_label_index)
    config = SamplingConfig(
        SamplingType.LINK, _norm_num_neighbors(num_neighbors),
        batch_size, shuffle, drop_last, with_edge, collect_features,
        neg_sampling is not None, False, data.edge_dir, seed)
    self._setup(data,
                EdgeSamplerInput(ei[0], ei[1], label=edge_label,
                                 input_type=edge_type,
                                 neg_sampling=neg_sampling),
                config, channel_size, num_workers, seed)


class _RemoteLoaderBase:
  """Shared remote (server-client) machinery: create one producer per
  server from a per-server sampler-input split, pull batches through
  the RemoteReceivingChannel, restart producers per epoch (reference:
  dist_loader.py:155-195 + dist_neighbor_loader.py remote branch).

  Resilience (docs/failure_model.md): a Heartbeat thread per server
  detects death in ~heartbeat_interval * heartbeat_miss seconds; a dead
  server's UNACKED seeds — its seed share minus the seeds of batches
  this loader already received (each batch message carries its seed ids
  in 'batch') — are redistributed across the surviving servers as fresh
  producers, so the epoch completes with every seed delivered exactly
  once. The server's worker_key idempotent-producer mechanism makes the
  re-requests safe. Degradations are counted in utils/trace.py
  ('resilience.failover', 'resilience.server_dead').

  This family is the PER-BATCH remote path (>= 2 RPC dispatches + host
  Python per step). For supervised homogeneous node classification the
  chunk-staged ``distributed.RemoteScanTrainer`` (docs/remote_scan.md)
  runs the same server-client topology at scanned speed — K-batch
  blocks, ceil(steps/K)+2 client dispatches, chunk-granular
  ack/failover — and is bit-identical to this path at shuffle=False.
  """

  #: Node loaders ack received seeds from each batch's 'batch' ids and
  #: can therefore fail over; link batches carry only local indices, so
  #: the link loader degrades to a hard error on server death.
  supports_failover = True

  def _tenant_kwargs(self) -> dict:
    """create_sampling_producer kwargs registering this loader's
    producers under its tenant — empty (wire-compatible with
    pre-tenancy servers) when no tenant is configured."""
    if getattr(self, '_tenant', None) is None:
      return {}
    return dict(tenant=self._tenant, priority=self._tenant_priority,
                weight=self._tenant_weight)

  def _note_throttle(self, rej):
    # remembered so an eventual idle-budget QueueTimeoutError names the
    # quota this tenant was last bouncing off (docs/multi_tenancy.md)
    self._last_throttle = rej

  def _setup_remote(self, config, per_server_inputs, worker_options):
    import dataclasses

    from ..channel import RemoteReceivingChannel
    from . import dist_client
    from .message import message_to_data
    from .resilience import Heartbeat
    self._message_to_data = message_to_data
    opts = worker_options
    self._opts = opts
    self._config = config
    self._tenant = getattr(opts, 'tenant', None) if opts else None
    self._tenant_priority = getattr(opts, 'tenant_priority', None) \
        if opts else None
    self._tenant_weight = getattr(opts, 'tenant_weight', None) \
        if opts else None
    self._bp_budget = getattr(opts, 'backpressure_budget', 120.0) \
        if opts else 120.0
    self._last_throttle = None   # last TenantRejection, for timeout context
    self.producer_ids = []
    self._expected = 0
    for i, (rank, part) in enumerate(zip(self.server_ranks,
                                         per_server_inputs)):
      # fold the SERVER index into the seed: same-ranked mp workers on
      # different servers would otherwise derive identical worker
      # seeds and draw identical negative edges per batch index
      # (negatives depend only on the graph + key)
      cfg_i = dataclasses.replace(
          config, seed=(config.seed or 0) * 7919 + i)
      pid = with_backpressure(
          lambda rank=rank, part=part, cfg_i=cfg_i:
          dist_client.request_server(
              rank, 'create_sampling_producer', part, cfg_i,
              opts.num_workers if opts else 1,
              worker_key=(opts.worker_key if opts else None),
              **self._tenant_kwargs()),
          describe=f'create_sampling_producer rank {rank}',
          budget_s=self._bp_budget, tenant=self._tenant,
          on_reject=self._note_throttle)
      self.producer_ids.append(pid)
      # the producer's own count: its mp workers split the seed share and
      # each rounds up, so ceil(n/batch_size) would undercount here
      exp = dist_client.request_server(
          rank, 'producer_num_expected', pid, idempotent=True)
      self._pair_expected = getattr(self, '_pair_expected', {})
      self._pair_expected[(rank, pid)] = exp
      self._expected += exp
    self.channel = RemoteReceivingChannel(
        self.server_ranks, self.producer_ids,
        prefetch_size=(opts.prefetch_size if opts else 4))
    self._dist_client = dist_client
    # -- resilience state ---------------------------------------------------
    # per-(rank, pid) seed shares for failover accounting (None when the
    # input carries no ackable seeds, e.g. link mode)
    self._pair_parts = {}
    for rank, pid, part in zip(self.server_ranks, self.producer_ids,
                               per_server_inputs):
      seeds = getattr(part, 'node', part if not hasattr(part, 'row')
                      else None)
      self._pair_parts[(rank, pid)] = (
          np.asarray(seeds).reshape(-1) if seeds is not None else None)
    self._dead_ranks = {}        # rank -> cause, sticky across epochs
    self._pair_batches = {}      # (rank, pid) -> batches received
    self._live_pairs = set()     # this epoch's pulling (rank, pid)s
    self._fo_producers = []      # this epoch's replacement (rank, pid)s
    self._fo_seq = 0
    self._epoch = 0
    self._heartbeat_miss = opts.heartbeat_miss if opts else 3
    self._heartbeat_interval = opts.heartbeat_interval if opts else 1.0
    self._failover_enabled = (opts.failover if opts else True) and \
        self.supports_failover
    self._idle_budget = opts.rpc_timeout if opts else 180.0
    probe_timeout = max(self._heartbeat_interval, 2.0)

    def probe(rank):
      from .resilience import NO_RETRY
      dist_client.request_server(rank, 'heartbeat',
                                 timeout=probe_timeout,
                                 idempotent=True, retry_policy=NO_RETRY)

    self._heartbeat = Heartbeat(
        self.server_ranks, probe, interval=self._heartbeat_interval,
        miss_threshold=self._heartbeat_miss)

  def _resolve_ranks(self, worker_options):
    opts = worker_options
    ranks = opts.server_rank if opts and opts.server_rank is not None \
        else [0]
    if isinstance(ranks, int):
      ranks = [ranks]
    self.server_ranks = list(ranks)

  def __len__(self):
    return self._expected

  # -- failover machinery ---------------------------------------------------

  def _ack(self, rank, pid, msg):
    """Record which seeds a received batch covered (homo: 'batch' ids;
    hetero: 'batch.<input_type>'). Unackable messages are ignored —
    failover then treats their seeds as undelivered (safe: duplicates
    are impossible, the pair's producer is abandoned before replay)."""
    self._pair_batches[(rank, pid)] = \
        self._pair_batches.get((rank, pid), 0) + 1
    acked = self._acked.get((rank, pid))
    if acked is None:
      acked = self._acked[(rank, pid)] = set()
    bs = msg.get('#META.batch_size')
    ids = msg.get('batch')
    if ids is None and '#META.input_type' in msg:
      t = bytes(np.asarray(msg['#META.input_type'])).decode()
      ids = msg.get(f'batch.{t}')
    if ids is None:
      return
    ids = np.asarray(ids).reshape(-1)
    if bs is not None:
      ids = ids[:int(np.asarray(bs).reshape(-1)[0])]
    acked.update(int(i) for i in ids)

  def _handle_dead_pair(self, rank, pid, cause):
    """Declare (rank, pid) dead and redistribute its unacked seeds to
    surviving servers. Returns buffered messages that were drained while
    abandoning the pair (already acked; caller yields them). Idempotent
    per pair per epoch."""
    if (rank, pid) in self._handled_pairs:
      return []
    # feasibility FIRST, before any state mutation: when this loader
    # cannot fail over, the rank must not be marked sticky-dead (a
    # transient blip would then poison every later epoch) and buffered
    # batches must not be drained onto the raise path
    part = self._pair_parts.get((rank, pid))
    if not self.supports_failover or part is None:
      raise RuntimeError(
          f'sampling server rank {rank} died mid-epoch ({cause}) and '
          'this loader cannot fail over: its batches carry no seed '
          'provenance to ack (link mode) — restart the epoch')
    if not self._failover_enabled:
      raise RuntimeError(
          f'sampling server rank {rank} died mid-epoch ({cause}) and '
          'failover is disabled (RemoteDistSamplingWorkerOptions'
          '.failover=False)')
    self._handled_pairs.add((rank, pid))
    # the failover span is the epoch tree's resilience annotation: the
    # degraded chunk of work — dead rank, cause, redistributed seed
    # count — hangs off this epoch's epoch.run span, and the replacement
    # producers' RPCs (and their workers' spans) parent under it
    from ..metrics import spans
    fo_span = spans.begin('loader.failover', rank=rank,
                          cause=str(cause)[:200])
    try:
      return self._handle_dead_pair_spanned(rank, pid, cause, part,
                                            fo_span)
    except BaseException as e:
      fo_span.attrs['error'] = f'{type(e).__name__}: {e}'
      raise
    finally:
      spans.end(fo_span)

  def _handle_dead_pair_spanned(self, rank, pid, cause, part, fo_span):
    from ..utils import trace
    self._live_pairs.discard((rank, pid))
    self._dead_ranks[rank] = cause
    self._heartbeat.mark_dead(rank, cause)
    self.channel.abandon(rank, pid)
    # ack everything already buffered from ANY pair before computing the
    # unacked set — in-flight batches of the dying server must not be
    # re-requested (they were delivered, just not consumed yet)
    buffered = self.channel.drain_now()
    for r2, p2, m in buffered:
      self._ack(r2, p2, m)
    acked = self._acked.get((rank, pid), set())
    unacked = part[~np.isin(part, np.fromiter(acked, dtype=part.dtype,
                                              count=len(acked)))] \
        if len(acked) else part
    survivors = [r for r in self.server_ranks
                 if r not in self._dead_ranks]
    if not survivors:
      raise RuntimeError(
          f'all sampling servers dead (last: rank {rank}: {cause}) — '
          'cannot complete the epoch')
    trace.counter_inc('resilience.failover')
    trace.counter_inc('resilience.failover_seeds', int(unacked.shape[0]))
    fo_span.attrs.update(seeds=int(unacked.shape[0]),
                         survivors=list(survivors))
    import logging
    logging.getLogger('graphlearn_tpu.loader').warning(
        'server rank %d dead (%s): redistributing %d unacked seeds '
        'across surviving servers %s', rank, cause, unacked.shape[0],
        survivors)
    if unacked.shape[0] == 0:
      return buffered
    import dataclasses
    from ..sampler import NodeSamplerInput as NSI
    new_expected = 0
    splits = np.array_split(unacked, len(survivors))
    for r2, sub in zip(survivors, splits):
      if sub.shape[0] == 0:
        continue
      self._fo_seq += 1
      base_key = (self._opts.worker_key
                  if self._opts and self._opts.worker_key else 'fo')
      key = (f'{base_key}/fo/e{self._epoch}/'
             f'd{rank}/s{r2}/{self._fo_seq}')
      part2 = (NSI(sub, self.input_type)
               if getattr(self, 'input_type', None) is not None else sub)
      cfg2 = dataclasses.replace(
          self._config,
          seed=(self._config.seed or 0) * 7919 + 104729 + self._fo_seq)
      # worker_key makes the create re-request-safe, so it may retry —
      # a transient hiccup on the SURVIVOR must not abort the very
      # failover meant to save the epoch. start_new_epoch_sampling has
      # no such dedup (a retried start double-produces), so it stays
      # single-attempt.
      pid2 = with_backpressure(
          lambda r2=r2, part2=part2, cfg2=cfg2, key=key:
          self._dist_client.request_server(
              r2, 'create_sampling_producer', part2, cfg2,
              self._opts.num_workers if self._opts else 1, worker_key=key,
              idempotent=True, **self._tenant_kwargs()),
          describe=f'failover producer rank {r2}',
          budget_s=self._bp_budget, tenant=self._tenant,
          on_reject=self._note_throttle)
      repl_expected = self._dist_client.request_server(
          r2, 'producer_num_expected', pid2, idempotent=True)
      self._dist_client.request_server(r2, 'start_new_epoch_sampling',
                                       pid2)
      self._pair_parts[(r2, pid2)] = sub
      self._pair_expected[(r2, pid2)] = repl_expected
      self._fo_producers.append((r2, pid2))
      self._live_pairs.add((r2, pid2))
      self.channel.add_producer(r2, pid2)
      new_expected += repl_expected
    # keep len(self) truthful mid-epoch: this epoch now delivers the
    # dead pair's already-received batches + the replacements' counts
    # instead of the dead pair's original expectation (re-chunking can
    # shift partial-batch counts when bs does not divide the shares)
    dead_expected = self._pair_expected.get((rank, pid))
    if dead_expected is not None:
      delivered = self._pair_batches.get((rank, pid), 0)
      self._expected += new_expected - (dead_expected - delivered)
    return buffered

  def __iter__(self):
    from ..metrics import flight, spans
    # Ordering matters: kill any previous epoch's pullers BEFORE
    # restarting the server producers (a stale puller would consume
    # new-epoch messages into its dead queue), and only then start the
    # new pullers.
    self.channel.stop(join=True)
    self._epoch += 1
    cfg = self._config
    tok = flight.epoch_begin()
    # the epoch span stays current across _epoch_messages, so the
    # start_new_epoch_sampling RPCs (and through them the servers'
    # producer workers) and any failover spans parent under it — one
    # joinable tree per epoch across client, server and producers
    sp = spans.begin('epoch.run', emitter=type(self).__name__,
                     epoch=self._epoch)
    received, completed = 0, False
    try:
      for data in self._epoch_messages():
        yield data
        received += 1
      completed = True
    finally:
      spans.end(sp, steps=received, completed=completed,
                dead_ranks=len(self._dead_ranks))
      # the flight record is the postmortem trail for THIS epoch:
      # failover/retry counter deltas, batches delivered, wall — one
      # JSONL line (docs/observability.md), nothing on the hot path
      # (cfg resolved before the brackets opened: nothing between the
      # span close above and the record below may raise)
      flight.end_for(
          self, tok, epoch=self._epoch, steps=received,
          completed=completed,
          config=dict(loader=type(self).__name__,
                      batch_size=cfg.batch_size, shuffle=cfg.shuffle,
                      num_neighbors=cfg.num_neighbors,
                      servers=list(self.server_ranks)),
          extra={'expected': self._expected,
                 'dead_ranks': {str(r): c for r, c in
                                self._dead_ranks.items()}})

  def _epoch_messages(self):
    import time as _time

    from ..channel import QueueTimeoutError
    from ..channel.remote_channel import PeerDeadError
    self._acked = {}
    self._pair_batches = {}
    self._handled_pairs = set()
    # failover producers are per-epoch: release last epoch's now (and
    # drop their seed-share records — a stale share must never be
    # redistributed into a later epoch)
    for rank, pid in self._fo_producers:
      self._pair_parts.pop((rank, pid), None)
      self._pair_expected.pop((rank, pid), None)
      try:
        self._dist_client.request_server(rank,
                                         'destroy_sampling_producer', pid)
      except (RuntimeError, ConnectionError, OSError):
        pass
    self._fo_producers = []
    # restore the undegraded expectation; this epoch's failovers (if
    # any) re-adjust it as they happen
    self._expected = sum(
        self._pair_expected.get(p, 0)
        for p in zip(self.server_ranks, self.producer_ids))
    started, start_dead = [], []
    for rank, pid in zip(self.server_ranks, self.producer_ids):
      if rank in self._dead_ranks:
        start_dead.append((rank, pid))
        continue
      try:
        self._dist_client.request_server(rank, 'start_new_epoch_sampling',
                                         pid)
        started.append((rank, pid))
      except (ConnectionError, TimeoutError, OSError) as e:
        if not (self._failover_enabled and self.supports_failover):
          # no recovery path: surface the failure without sticky-marking
          # the rank, so a recovered server works on the next attempt
          raise
        start_dead.append((rank, pid))
        self._dead_ranks[rank] = repr(e)
    if not started:
      raise RuntimeError('no live sampling server to start the epoch: '
                         f'dead={self._dead_ranks}')
    self._live_pairs = set(started)
    self.channel.start_pairs(started)
    self._heartbeat.start()
    # ranks that died in an earlier epoch (or refused the epoch start):
    # their whole seed share is unacked — fail it over immediately
    for rank, pid in start_dead:
      for r2, p2, m in self._handle_dead_pair(
          rank, pid, self._dead_ranks.get(rank, 'dead at epoch start')):
        yield self._message_to_data(m)
    idle_since = _time.monotonic()
    while True:
      try:
        rank, pid, msg = self.channel.recv_with_meta(timeout_ms=5000)
      except StopIteration:
        return
      except PeerDeadError as e:
        for r2, p2, m in self._handle_dead_pair(e.rank, e.producer_id,
                                                e.cause):
          yield self._message_to_data(m)
        continue
      except QueueTimeoutError as qte:
        # quiet window: consult liveness before waiting further — a
        # partitioned/hung server never RSTs, the heartbeat is the only
        # signal (detection in seconds vs the 180 s socket timeout)
        handled = False
        for rank, cause in self._heartbeat.dead_ranks().items():
          for (r2, p2) in [pr for pr in list(self._live_pairs)
                           if pr[0] == rank and
                           pr not in self._handled_pairs]:
            for r3, p3, m in self._handle_dead_pair(r2, p2, cause):
              yield self._message_to_data(m)
            handled = True
        if handled:
          idle_since = _time.monotonic()
          continue
        if _time.monotonic() - idle_since > self._idle_budget:
          # a starved tenant's stall must name WHO hit WHAT limit, not
          # read as an anonymous timeout (docs/multi_tenancy.md)
          last = getattr(self, '_last_throttle', None)
          qte.with_context(tenant=getattr(self, '_tenant', None),
                           quota=getattr(last, 'quota', None))
          raise
        continue
      idle_since = _time.monotonic()
      self._ack(rank, pid, msg)
      yield self._message_to_data(msg)

  def shutdown(self):
    self._heartbeat.stop()
    self.channel.stop()
    for rank, pid in (list(zip(self.server_ranks, self.producer_ids)) +
                      list(self._fo_producers)):
      if rank in self._dead_ranks:
        continue
      try:
        self._dist_client.request_server(rank,
                                         'destroy_sampling_producer', pid)
      except (RuntimeError, ConnectionError, OSError):
        pass


class RemoteDistNeighborLoader(_RemoteLoaderBase):
  """Remote (server-client) NODE loading: producers run on sampling
  servers, batches stream back over RPC; hetero seeds as
  ('ntype', ids)."""

  def __init__(self, num_neighbors, input_nodes,
               batch_size: int = 64, shuffle: bool = False,
               drop_last: bool = False, with_edge: bool = False,
               collect_features: bool = True, worker_options=None,
               seed: Optional[int] = None):
    from ..sampler import NodeSamplerInput as NSI
    from ..sampler import SamplingConfig, SamplingType
    self._resolve_ranks(worker_options)
    # hetero seeds: ('paper', ids) — the server's mp workers run the
    # typed engine and stream HeteroData messages back (round 5); ship
    # typed NodeSamplerInputs so the tuple convention (type FIRST)
    # never hits CastMixin's positional cast
    input_type, input_nodes = _split_input_type(input_nodes)
    # stored for failover: replacement producers must re-ship TYPED
    # seeds, or the server-side producer rejects them for hetero graphs
    self.input_type = input_type
    config = SamplingConfig(
        SamplingType.NODE, _norm_num_neighbors(num_neighbors),
        batch_size, shuffle, drop_last, with_edge, collect_features,
        False, False, 'out', seed)
    seeds = np.asarray(input_nodes).reshape(-1)
    # split seeds across servers; each server samples its share
    splits = np.array_split(seeds, len(self.server_ranks))
    parts = [NSI(p, input_type) if input_type is not None else p
             for p in splits]
    self._setup_remote(config, parts, worker_options)


class RemoteDistLinkNeighborLoader(_RemoteLoaderBase):
  """Remote (server-client) LINK loading: seed edges split across the
  sampling servers, whose mp workers draw negatives + run the (typed)
  link engine; batches stream back with edge_label metadata. Hetero
  seed edges as ((src_t, rel, dst_t), [2, E])."""

  # link batches expose only batch-local seed indices — no global edge
  # ids to ack — so a dead server is a hard error here, not a failover
  supports_failover = False

  def __init__(self, num_neighbors, edge_label_index, edge_label=None,
               neg_sampling=None, batch_size: int = 64,
               shuffle: bool = False, drop_last: bool = False,
               with_edge: bool = False, collect_features: bool = True,
               worker_options=None, seed: Optional[int] = None):
    from ..sampler import (EdgeSamplerInput, NegativeSampling,
                           SamplingConfig, SamplingType)
    self._resolve_ranks(worker_options)
    edge_type, edge_label_index = _split_edge_type(edge_label_index)
    ei = np.asarray(edge_label_index)
    label = (np.asarray(edge_label).reshape(-1)
             if edge_label is not None else None)
    ns = (NegativeSampling.cast(neg_sampling)
          if neg_sampling is not None else None)
    config = SamplingConfig(
        SamplingType.LINK, _norm_num_neighbors(num_neighbors),
        batch_size, shuffle, drop_last, with_edge, collect_features,
        ns is not None, False, 'out', seed)
    nsrv = len(self.server_ranks)
    row_s = np.array_split(ei[0].reshape(-1), nsrv)
    col_s = np.array_split(ei[1].reshape(-1), nsrv)
    lab_s = (np.array_split(label, nsrv) if label is not None
             else [None] * nsrv)
    parts = [EdgeSamplerInput(r, c, label=lb, input_type=edge_type,
                              neg_sampling=ns)
             for r, c, lb in zip(row_s, col_s, lab_s)]
    self._setup_remote(config, parts, worker_options)


class DistLinkNeighborLoader(DistLoader):
  """Distributed link-prediction loader: per-shard seed-edge blocks ->
  one SPMD link-sampling program (reference:
  distributed/dist_link_neighbor_loader.py:1-158; the sampling itself is
  dist_neighbor_sampler.py:369-496).

  Args:
    edge_label_index: [2, E] seed edges, or (edge_type, [2, E]) for
      hetero.
    edge_label: optional [E] labels for the positives.
    neg_sampling: optional NegativeSampling ('binary'/'triplet').
  """

  def __init__(self, data: DistDataset, num_neighbors, edge_label_index,
               edge_label=None, batch_size: int = 64,
               shuffle: bool = False, drop_last: bool = True,
               neg_sampling=None, with_edge: bool = False,
               collect_features: bool = True, seed: Optional[int] = None,
               node_budget: Optional[int] = None, mesh=None,
               with_weight: bool = False, dedup: str = 'sort',
               bucket_frac=2.0, neg_strict: bool = False,
               frontier_caps=None, overflow_policy: str = 'raise'):
    if mesh is None:
      from .dist_context import get_context
      ctx = get_context()
      mesh = ctx.mesh if ctx else None
    input_type, edge_label_index = _split_edge_type(edge_label_index)
    ei = np.asarray(edge_label_index)
    self.seed_rows = ei[0].reshape(-1)
    self.seed_cols = ei[1].reshape(-1)
    self.edge_label = (np.asarray(edge_label).reshape(-1)
                       if edge_label is not None else None)
    self.neg_sampling = neg_sampling
    # frontier_caps: calibrate against the effective PER-SHARD seed
    # width — the engine derives it internally from batch_size and
    # neg_sampling (calibrate.link_seed_width); pass caps estimated at
    # that width
    sampler = DistNeighborSampler(
        data.graph, num_neighbors, mesh,
        dist_feature=data.node_features, with_edge=with_edge, seed=seed,
        node_budget=node_budget, collect_features=collect_features,
        with_weight=with_weight, dedup=dedup, bucket_frac=bucket_frac,
        neg_strict=neg_strict, frontier_caps=frontier_caps)
    super().__init__(data, sampler, np.zeros(0, np.int64), batch_size,
                     shuffle, drop_last, collect_features, seed,
                     overflow_policy=overflow_policy)
    self.input_type = input_type  # EdgeType for hetero link sampling

  def _num_seeds(self):
    return self.seed_rows.shape[0]

  def __iter__(self):
    from ..sampler import EdgeSamplerInput
    # overflow-policy prologue BEFORE the span/flight bracket: a raise
    # from it must not leak the attached epoch.run span (same ordering
    # as DistLoader.__iter__)
    guarded, recompute = self._overflow_epoch_start()
    tok = self._flight_begin()
    steps, completed = 0, False
    try:
      for idx, mask in self._index_blocks():
        inputs = EdgeSamplerInput(
            self.seed_rows[idx], self.seed_cols[idx],
            label=(self.edge_label[idx]
                   if self.edge_label is not None else None),
            input_type=self.input_type,
            neg_sampling=self.neg_sampling)
        if recompute:
          keys = self.sampler._next_keys()
          out = self.sampler.sample_from_edges(inputs, seed_mask=mask,
                                               keys=keys)
          if self._batch_overflowed(out):
            self.overflow_recomputes += 1
            out = self._replay_sampler().sample_from_edges(
                inputs, seed_mask=mask, keys=keys)
        else:
          out = self.sampler.sample_from_edges(inputs, seed_mask=mask)
          if guarded:
            self._accumulate_overflow(out)
        yield self._collate_fn(out)
        steps += 1
      completed = True
      if guarded and not recompute:
        self._finish_epoch_overflow()
    finally:
      # device-fetch publish can raise: close span + flight regardless
      try:
        self._publish_feature_stats()
      finally:
        self._flight_end(tok, steps, completed)


class DistSubGraphLoader(DistLoader):
  """Distributed induced-subgraph loader (reference:
  distributed/dist_subgraph_loader.py:1-93; sampling is
  dist_neighbor_sampler.py:499-559). ``num_neighbors=None`` induces over
  the seed set alone; otherwise seeds are hop-expanded first."""

  def __init__(self, data: DistDataset, num_neighbors, input_nodes,
               batch_size: int = 64, shuffle: bool = False,
               drop_last: bool = True, with_edge: bool = False,
               collect_features: bool = True, seed: Optional[int] = None,
               max_degree: Optional[int] = None, mesh=None,
               bucket_frac=2.0):
    if mesh is None:
      from .dist_context import get_context
      ctx = get_context()
      mesh = ctx.mesh if ctx else None
    sampler = DistNeighborSampler(
        data.graph, num_neighbors, mesh,
        dist_feature=data.node_features, with_edge=with_edge, seed=seed,
        collect_features=collect_features, bucket_frac=bucket_frac)
    super().__init__(data, sampler, input_nodes, batch_size, shuffle,
                     drop_last, collect_features, seed)
    self.max_degree = max_degree

  def __iter__(self):
    tok = self._flight_begin()
    steps, completed = 0, False
    try:
      for idx, mask in self._index_blocks():
        out = self.sampler.subgraph(self.input_seeds[idx],
                                    seed_mask=mask,
                                    max_degree=self.max_degree)
        yield self._collate_fn(out)
        steps += 1
      completed = True
    finally:
      # device-fetch publish can raise: close span + flight regardless
      try:
        self._publish_feature_stats()
      finally:
        self._flight_end(tok, steps, completed)


class DistNeighborLoader(DistLoader):
  """Reference: dist_neighbor_loader.py:104-112."""

  def __init__(self, data: DistDataset, num_neighbors: List[int],
               input_nodes, batch_size: int = 64, shuffle: bool = False,
               drop_last: bool = True, with_edge: bool = False,
               collect_features: bool = True, seed: Optional[int] = None,
               node_budget: Optional[int] = None, mesh=None,
               with_weight: bool = False, dedup: str = 'sort',
               seed_labels_only: bool = False, bucket_frac=2.0,
               frontier_caps=None, overflow_policy: str = 'raise'):
    if mesh is None:
      from .dist_context import get_context
      ctx = get_context()
      mesh = ctx.mesh if ctx else None
    sampler = DistNeighborSampler(
        data.graph, num_neighbors, mesh,
        dist_feature=data.node_features, with_edge=with_edge, seed=seed,
        node_budget=node_budget, collect_features=collect_features,
        with_weight=with_weight, dedup=dedup, bucket_frac=bucket_frac,
        frontier_caps=frontier_caps)
    super().__init__(data, sampler, input_nodes, batch_size, shuffle,
                     drop_last, collect_features, seed,
                     seed_labels_only=seed_labels_only,
                     overflow_policy=overflow_policy)
