"""The batch sources the fused epoch executors share.

The reference decouples sampling from training with an asynchronous
producer-consumer pipeline
(/root/reference/graphlearn_torch/python/distributed/dist_sampling_producer.py:53-151).
A TPU core runs one XLA program at a time, so here the sampler, the
collate gather and the train step are traced into ONE program and the
host only dispatches. This module holds the two pieces those programs
are built from:

* :class:`FusedEpochTrainer` — the in-program batch source of the
  single-chip scanned trainers (``scan_epoch.ScanTrainer``,
  ``run_epoch.RunTrainer``, ``storage.TieredScanTrainer``): scope
  validation, the device feature/label tables and the pure
  sample+collate body, for a homogeneous or a typed loader.
* :class:`DistFusedEpochTrainer` — its mesh counterpart and the
  per-step trainer of the collocated mesh: the data-parallel train
  step and the traced sample+collate body that
  ``scan_epoch.DistScanTrainer`` scans.

The host loops stay dispatch-only (no device->host fetch in the hot
loop); losses stay on device and are fetched once an epoch.
"""
from typing import Optional

from .. import ops
from ..metrics.registry_names import (SCOPE_ALLREDUCE, SCOPE_FWD_BWD,
                                      SCOPE_SAMPLE, SCOPE_SEEDS,
                                      SCOPE_TRAIN, SCOPE_UPDATE)
from .link_loader import LinkLoader
from .node_loader import NodeLoader

_RECOMPUTE_MSG = (
    "overflow_policy='recompute' needs a device->host sync per batch, "
    'which defeats the overlapped pipeline. Use the plain loader loop '
    "for recompute, or overflow_policy='raise'/'warn' here (the flag "
    'accumulates on device and is checked once at epoch end).')

_DIST_REMOTE_MSG = (
    'scanned/fused distributed epochs are COLLOCATED-MESH only: pass a '
    'DistNeighborLoader over the training mesh. Remote (server-client) '
    'loaders have their own scanned path — distributed.'
    'RemoteScanTrainer, the chunk-staged hybrid (docs/remote_scan.md): '
    'sampling servers replay the counter-addressed stream into K-batch '
    'blocks, the client double-buffers block c+1 over RPC while chunk '
    'c trains, and acks/failover run at CHUNK granularity — exact '
    'even under shuffle=True, whose epoch permutation is a pure '
    'function of (seed, epoch) that survivors replay identically. '
    'Mp-worker loaders keep the '
    'per-step host loop: their worker-restart replay acks batches one '
    'by one (docs/failure_model.md).')


def refuse_typed(loader, name: str):
  """For the executors whose epoch loop keeps the homogeneous key stream
  (one key a batch, where a typed batch takes ``_key_stride``): called
  first in their ``__init__``."""
  if getattr(loader.sampler, 'is_hetero', False):
    from ..sampler.capacity import CapacityPlanError
    raise CapacityPlanError(
        name, 'its epoch loop keeps the homogeneous key stream',
        'typed graphs scan through loader.ScanTrainer')


def refuse_link(loader, name: str):
  """For the executors whose chunk programs keep the node job's step
  contract (seeds, labels, cross-entropy): called first in their
  ``__init__``."""
  if isinstance(loader, LinkLoader):
    raise ValueError(
        f'{name} scans node-seeded jobs: its chunk program slices a '
        '[steps, batch] matrix of seed NODES and trains on node labels. '
        'An edge-seeded job (seed pairs, negatives drawn in the program, '
        'the pair loss) scans through loader.ScanTrainer')


class FusedEpochTrainer:
  """Shared plumbing for the fused epoch executors
  (scan_epoch.ScanTrainer and its subclasses): scope validation, the
  device feature/label tables, and the pure sample+collate body they
  trace into their programs.

  Requirements: fused sampler, device-resident feature tables, no edge
  features. The STEP CONTRACT — what a step's seeds are, what the batch
  source hands the chunk and which loss reads it — is read from the
  loader's kind: a node loader gives seed nodes, node labels and the
  masked cross-entropy step (``num_classes`` wide); a link loader
  (``LinkNeighborLoader``) gives seed PAIRS by position in its
  ``edge_label_index``, its ``NegativeSampling``, the sampler's link
  body (``NeighborSampler._link_body``: negatives, seed union,
  expansion, ``edge_label_index`` through ``seed_inverse``) and the pair
  step (``train.make_link_train_step``'s loss), and is asked for no
  ``num_classes``. Where a node job's batches come from
  is read from the loader's sampler: a homogeneous graph gives the
  fused multi-hop program + ``ops.collate_batch``; a typed graph with
  seeds of ONE node type gives the
  typed hop loop (``NeighborSampler._typed_fn``) +
  ``ops.collate_typed_batch`` over per-type tables, closed by the
  sampler's plan (dict-form ``frontier_caps``). Everything after the
  batch — the train step, the epoch loop, hooks, recovery — is shared.
  """

  _NAME = 'FusedEpochTrainer'

  def __init__(self, loader: NodeLoader, model, tx,
               num_classes: Optional[int] = None,
               seed_labels_only: Optional[bool] = None):
    sampler = loader.sampler
    typed = bool(getattr(sampler, 'is_hetero', False))
    link = isinstance(loader, LinkLoader)
    if not sampler.fused:
      raise ValueError(f'{self._NAME} needs the fused sampler path')
    if sampler.with_edge:
      raise ValueError('with_edge batches are not supported in the '
                       'fused epoch programs')
    if getattr(sampler, 'clamped_exact', False) and \
        loader.overflow_policy == 'recompute':
      raise ValueError(_RECOMPUTE_MSG)
    self.loader = loader
    self.model = model
    self.num_classes = num_classes
    self._sampler = sampler
    self._batch_size = loader.batch_size
    if seed_labels_only is None:
      seed_labels_only = loader.seed_labels_only
    self._label_cap = self._batch_size if seed_labels_only else None
    self._input_type = loader.input_type if typed and not link else None
    from ..models import train as train_lib
    if link:
      self._init_link_source(loader, typed)
      self._train_step, _ = train_lib.make_link_train_step(model, tx)
      self._sample_collate = self._make_link_sample_collate_body()
      return
    if typed:
      self._init_typed_source(loader)
    else:
      fanouts = tuple(sampler.num_neighbors)
      self._sample_fn = sampler._homo_fn(self._batch_size, fanouts)
      self._key_stride = 1
      self._feats, self._id2i = self._resolve_feature_tables(loader)
    self._labels = loader._label_table(self._input_type)
    if self._labels is None:
      raise ValueError(f'{self._NAME} needs node labels')
    if num_classes is None:
      raise ValueError(f'{self._NAME}: a node job trains on node labels '
                       'by cross-entropy and needs num_classes')

    self._train_step, _ = train_lib.make_train_step(model, tx, num_classes)
    self._sample_collate = self._make_sample_collate_body()

  def _resolve_feature_tables(self, loader):
    """(feats, id2index) device tables the traced programs gather from.
    The base contract is an ALL-HBM table; the out-of-core trainer
    (storage/scan.py TieredScanTrainer) overrides this to accept a
    TieredFeature's hot prefix + per-chunk staged slabs."""
    dt = loader.data.node_features.device_table() \
        if loader.data.node_features is not None else None
    if dt is None:
      raise ValueError(f'{self._NAME} needs a device-resident '
                       'feature table (Feature on HBM), or the tiered '
                       'trainer (storage.TieredScanTrainer) for an '
                       'out-of-core TieredFeature')
    return dt

  def _init_typed_source(self, loader):
    """The typed batch source: seeds of ONE node type (``loader.
    input_type``), the typed hop loop as one program, and per-type
    device tables for every node type the plan gives rows to."""
    from ..sampler.capacity import CapacityPlan
    t_in = self._input_type
    if t_in is None:
      raise ValueError(f'{self._NAME}: a typed graph needs seeds of one '
                       "node type — pass input_nodes=('<ntype>', ids)")
    sampler = self._sampler
    plan = CapacityPlan.from_sampler(sampler, self._batch_size,
                                     input_type=t_in)
    self._sample_fn = sampler._typed_fn(self._batch_size, t_in)
    # one fold_in count per (hop, edge type) touch, as the per-batch
    # typed loader draws them: step g's touches are count0 + g*stride + j
    self._key_stride = plan.key_draws_per_batch
    stores = loader.data.node_features
    stores = stores if isinstance(stores, dict) else {}
    feats, id2i = {}, {}
    for t in plan.feat_types(available=stores):
      dt = stores[t].device_table()
      if dt is None:
        raise ValueError(f'{self._NAME} needs a device-resident feature '
                         f'table for node type {t!r} (Feature on HBM)')
      feats[t], id2i[t] = dt
    if not feats:
      raise ValueError(f'{self._NAME} needs device-resident feature '
                       'tables (Feature on HBM)')
    self._feats, self._id2i = feats, id2i

  def _init_link_source(self, loader, typed: bool):
    """The edge-seeded batch source: ``batch_size`` seed pairs a step,
    gathered on the device from the loader's ``edge_label_index`` by the
    epoch order's positions, then the sampler's link body. What the
    chunk does not run yet is refused here, each by its mechanism."""
    neg = loader.neg_sampling
    if typed:
      from ..sampler.capacity import CapacityPlanError
      raise CapacityPlanError(
          self._NAME, 'a typed link batch seeds TWO node types (the '
          "seed edge type's ends), and the chunk's typed source takes "
          'seeds of one', 'iterate the typed LinkNeighborLoader per batch')
    if neg is not None and not neg.is_binary():
      raise ValueError(
          f'{self._NAME}: the pair step reads edge_label_index / '
          'edge_label; a triplet batch carries src / dst_pos / dst_neg '
          'indices, for which models.train has no step — iterate the '
          'loader per batch with your own margin loss')
    if loader.edge_label is not None:
      raise ValueError(
          f'{self._NAME}: the chunk gathers a step\'s seed pairs by '
          'position and labels them ones (then zeros for the negatives); '
          'a caller\'s edge_label array is not gathered beside them — '
          'iterate the loader per batch')
    if len(loader.rows) % self._batch_size and not \
        loader._batcher.drop_last:
      raise ValueError(
          f'{self._NAME}: a scanned link step has one static width, and '
          'the per-batch loop draws num_negatives(tail) negatives for a '
          'short last batch — pass drop_last=True, or a seed set that '
          'batch_size divides')
    self._neg_sampling = neg
    self._link_body = self._sampler._link_body(self._batch_size, neg)
    self._key_stride = 1
    self._feats, self._id2i = self._resolve_feature_tables(loader)
    # the table the chunk reads beside the features: a node job's node
    # labels, a link job's seed pairs (rows, cols), device arrays
    self._labels = loader.seed_pairs_device()

  def _make_link_sample_collate_body(self):
    """The link job's traced sample+collate body: ``pairs`` (the loader's
    seed-edge arrays) and ``pos`` (the step's positions in them) where
    the node body takes labels and seed ids. Returns ``(batch, overflow,
    link_counts)``; the batch is ``train.link_batch_to_dict``'s."""
    import jax
    link_body = self._link_body

    def _sample_collate(gargs, feats, id2i, pairs, pos, pmask, key):
      del pmask   # every slot is a seed edge (no ragged tail: __init__)
      with jax.named_scope(SCOPE_SAMPLE), jax.named_scope(SCOPE_SEEDS):
        rows, cols = pairs[0][pos], pairs[1][pos]
      res = link_body(gargs, rows, cols, key)
      col = ops.collate_batch(res['node'], res['num_nodes'], res['row'],
                              res['col'], feats, id2i, None, None, None)
      batch = dict(x=col['x'], edge_index=col['edge_index'],
                   edge_mask=res['edge_mask'], **res['link'])
      return batch, res['overflow'], res['link_counts']

    return _sample_collate

  def _step_keys(self, base_key, count):
    """What the traced body samples step ``count`` with: the sampler's
    own fold_in stream — one key on a homogeneous graph, the [S, 2]
    per-touch keys ``fold_in(base_key, count + j)`` on a typed one."""
    import jax
    import jax.numpy as jnp
    if self._input_type is None:
      return jax.random.fold_in(base_key, count)
    return jax.vmap(lambda j: jax.random.fold_in(base_key, count + j))(
        jnp.arange(self._key_stride, dtype=jnp.int32))

  def _make_sample_collate_body(self):
    """The pure traced sample+collate body. ``feats`` is whatever
    pytree :meth:`_resolve_feature_tables` produced — here a plain
    [N, F] table fed straight to the fused collate gather. On a typed
    graph ``fargs`` is ``sampler._typed_args()``, ``feats`` / ``id2i``
    the per-type table dicts, ``labels`` the seed type's table and
    ``key`` the step's [S, 2] per-touch keys."""
    sample_fn, label_cap = self._sample_fn, self._label_cap
    t_in = self._input_type

    def _sample_collate(fargs, feats, id2i, labels, seeds, smask, key):
      if t_in is None:
        res = sample_fn(*fargs, seeds, smask, key)
        col = ops.collate_batch(res['node'], res['num_nodes'], res['row'],
                                res['col'], feats, id2i, labels, None,
                                None, label_cap=label_cap)
        x, edge_index, y = col['x'], col['edge_index'], col['y']
        num_seed_nodes = res['num_sampled_nodes'][0]
      else:
        res = sample_fn(fargs, seeds, smask, key)
        x, edge_index, y = ops.collate_typed_batch(
            res['node'], res['row'], res['col'], feats, id2i, labels,
            t_in, label_cap=label_cap)
        num_seed_nodes = res['num_sampled_nodes'][t_in][0]
      batch = dict(x=x, edge_index=edge_index, edge_mask=res['edge_mask'],
                   y=y, num_seed_nodes=num_seed_nodes)
      # the calibrated-caps truncation flag rides OUTSIDE the batch dict
      # (train_step must not see it; the batch buffers are donated)
      return batch, res['overflow']

    return _sample_collate

  def _sample_args(self):
    """The sampler's graph device arrays for the traced body, re-read
    each epoch (a padded-table reseed must reach the chunks)."""
    if isinstance(self.loader, LinkLoader):
      return self._sampler._link_args(self._neg_sampling)
    if self._input_type is not None:
      return self._sampler._typed_args()
    return self._sampler._fused_args()


class DistFusedEpochTrainer:
  """Shared plumbing for the DISTRIBUTED fused-epoch executors
  (scan_epoch.DistScanTrainer and its per-step reference loop): scope
  validation, the data-parallel train-step body (per-shard grads ->
  pmean over every mesh axis -> optax update), and the traced
  sample+collate body both the scanned chunks and the per-step program
  compose.

  Scope: a COLLOCATED homogeneous or heterogeneous DistNeighborLoader
  with feature collection and node labels (supervised node
  classification on the mesh — the distributed counterpart of
  FusedEpochTrainer's scope). Remote/mp loaders are rejected
  (``_DIST_REMOTE_MSG``): their failover contract needs per-batch host
  visibility. ``overflow_policy='recompute'`` is rejected exactly like
  the local trainers (per-batch host sync).
  """

  _NAME = 'DistFusedEpochTrainer'

  def __init__(self, loader, model, tx, num_classes: int,
               seed_labels_only: Optional[bool] = None):
    from ..distributed.dist_loader import (DistLinkNeighborLoader,
                                           DistLoader, DistSubGraphLoader)
    from ..models import train as train_lib
    if not isinstance(loader, DistLoader):
      raise ValueError(f'{self._NAME}: {type(loader).__name__} is not a '
                       f'collocated DistLoader. {_DIST_REMOTE_MSG}')
    if isinstance(loader, DistLinkNeighborLoader):
      raise ValueError(
          f'{self._NAME} covers supervised NODE classification; link '
          'loaders keep the per-step loop — link batches train on '
          'edge_label metadata the fused chunk program does not '
          'collate, and they carry no per-seed ack provenance for any '
          'chunk- or batch-granular failover (docs/failure_model.md '
          "'Limits'; the chunk-staged remote path, "
          'distributed.RemoteScanTrainer, is node-only for the same '
          'reason)')
    if isinstance(loader, DistSubGraphLoader):
      raise ValueError(
          f'{self._NAME} covers supervised NODE classification; '
          'subgraph loaders yield induced subgraphs with no '
          'train-step contract to fuse into a scanned chunk — '
          'iterate them per step')
    if loader.overflow_policy == 'recompute':
      raise ValueError(_RECOMPUTE_MSG)
    sampler = loader.sampler
    if sampler.with_edge:
      raise ValueError('with_edge batches are not supported in the '
                       'fused distributed epoch programs')
    if getattr(loader.data, 'edge_features', None):
      raise ValueError(f'{self._NAME} does not collate edge features; '
                       'use the per-step loader loop')
    if not loader.collect_features or sampler.dist_feature is None:
      raise ValueError(f'{self._NAME} needs collect_features=True and a '
                       'DistFeature store (the fused program inlines the '
                       'cached miss-only lookup)')
    if loader.data.node_labels is None:
      raise ValueError(f'{self._NAME} needs node labels')
    self.loader = loader
    self.model = model
    self.tx = tx
    self.num_classes = num_classes
    self._sampler = sampler
    self.is_hetero = sampler.is_hetero
    self.mesh = sampler.mesh
    self._axes = sampler._axes
    self._axis_sizes = sampler._axis_sizes
    self._nparts = loader.num_partitions
    self._batch_size = loader.batch_size    # per shard
    if seed_labels_only is None:
      seed_labels_only = loader.seed_labels_only
    self._label_cap = self._batch_size if seed_labels_only else None
    if self.is_hetero:
      self._input_type = loader.input_type
      assert self._input_type is not None, \
          'hetero distributed training requires typed seeds'
      labels = loader.data.node_labels
      if not isinstance(labels, dict) or self._input_type not in labels:
        raise ValueError(f'{self._NAME} needs labels for the seed type '
                         f'{self._input_type!r}')
      self._label_store = sampler._label_dist(labels[self._input_type],
                                              self._input_type)
      self._feat = dict(sampler.dist_feature)
    else:
      self._input_type = None
      self._label_store = sampler._label_dist(loader.data.node_labels)
      self._feat = sampler.dist_feature
    self._loss_fn = train_lib.make_loss_fn(model, num_classes)
    self._train_state_cls = train_lib.TrainState
    self._step_fn = None   # built lazily (first per-step train_step)

  # -------------------------------------------------------- traced bodies

  def _dp_step_body(self, state, batch):
    """Per-shard data-parallel update (traced): grads/loss/acc pmean'd
    over EVERY mesh axis — the SPMD analog of the reference's DDP
    allreduce — then one optax update of the replicated state."""
    import jax
    import optax
    with jax.named_scope(SCOPE_TRAIN):
      with jax.named_scope(SCOPE_FWD_BWD):
        (loss, acc), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(state.params, batch)
      with jax.named_scope(SCOPE_ALLREDUCE):
        grads = jax.lax.pmean(grads, self._axes)
        loss = jax.lax.pmean(loss, self._axes)
        acc = jax.lax.pmean(acc, self._axes)
      with jax.named_scope(SCOPE_UPDATE):
        updates, opt_state = self.tx.update(grads, state.opt_state,
                                            state.params)
        params = optax.apply_updates(state.params, updates)
    return self._train_state_cls(params, opt_state, state.step + 1), \
        loss, acc

  def _make_sample_collate(self):
    """Traced per-shard sample -> feature/label collate body shared by
    the scanned chunks (scan_epoch.DistScanTrainer) — the in-program
    equivalent of loader.__iter__'s sample_from_nodes + _collate_fn
    path, threading the feature-cache stats rows instead of the
    device-resident accumulator.

    Returns ``(shard_tree, repl_tree, body)`` where ``body(views, repl,
    stats_rows, seeds, smask, key) -> (batch, overflow,
    new_stats_rows, exchange_rows)`` (``exchange_rows``: the [hops]
    frontier ids this shard sent to other shards, empty on a typed
    graph); ``views`` is the per-shard ([0]-indexed) view of
    ``shard_tree`` and the trees are the device arrays to feed the
    enclosing shard_map (every ``shard_tree`` leaf takes spec P(axes),
    every ``repl_tree`` leaf P())."""
    import jax.numpy as jnp
    sampler = self._sampler
    b = self._batch_size
    label_cap = self._label_cap
    if self.is_hetero:
      return self._make_hetero_sample_collate()
    from ..distributed.dist_neighbor_sampler import _homo_hop_loop
    fanouts = tuple(sampler.num_neighbors)
    caps = sampler._capacities(b)
    node_cap = sampler._node_cap(caps)
    dedup = sampler.dedup
    weighted = sampler._weighted_for()
    bucket_frac = sampler.bucket_frac
    ax, sizes, nparts = self._axes, self._axis_sizes, self._nparts
    feat_body = self._feat._shard_body(node_cap)
    lab_body = self._label_store._shard_body(
        label_cap if label_cap is not None else node_cap)
    d = sampler._dev
    gsh = sampler.graph_shards()
    index = sampler.row_index_statics()
    fdev = self._feat.device_arrays()
    ldev = self._label_store.device_arrays()
    shard_keys = self._label_store.SHARD_KEYS
    repl_keys = self._label_store.REPL_KEYS
    table_args = self._label_store.table_args
    shard_tree = dict(
        g=gsh,
        f={k: fdev[k] for k in shard_keys},
        l={k: ldev[k] for k in shard_keys})
    repl_tree = dict(
        pb=d['node_pb'],
        f={k: fdev[k] for k in repl_keys},
        l={k: ldev[k] for k in repl_keys})

    def body(views, repl, stats_rows, seeds, smask, key):
      res = _homo_hop_loop(views['g'], repl['pb'], seeds, smask, key,
                           fanouts, caps, node_cap, nparts, False,
                           weighted, dedup=dedup,
                           bucket_frac=bucket_frac, axes=ax,
                           axis_sizes=sizes, index=index)
      ids = res['node']
      x, srow = feat_body(*table_args(views['f'], repl['f']),
                          stats_rows, ids, ids >= 0)
      lab_ids = ids[:label_cap] if label_cap is not None else ids
      y, _ = lab_body(*table_args(views['l'], repl['l']),
                      jnp.zeros((4,), jnp.int32), lab_ids, lab_ids >= 0)
      batch = dict(x=x,
                   edge_index=jnp.stack([res['row'], res['col']]),
                   edge_mask=res['edge_mask'], y=y[:, 0],
                   num_seed_nodes=res['num_sampled_nodes'][0])
      return batch, res['overflow'], srow, res['exchange_rows']

    return shard_tree, repl_tree, body

  def _make_hetero_sample_collate(self):
    """Typed counterpart of _make_sample_collate: the engine's typed
    hop loop + per-ntype cached feature lookups (stats row per store) +
    the seed type's label gather."""
    import jax.numpy as jnp
    sampler = self._sampler
    b = self._batch_size
    label_cap = self._label_cap
    t_in = self._input_type
    plan = sampler._hetero_plan({t_in: b})
    _, _, node_caps = plan
    feat_types = [t for t in sampler.graph.ntypes
                  if node_caps.get(t, 0) > 0 and t in self._feat]
    # the stores whose [4] stats rows thread the scan carry (one per
    # sampled, feature-bearing ntype) — DistScanTrainer reads this to
    # shape the carry and write the accumulators back per epoch
    self._feat_types = feat_types
    feat_bodies = {t: self._feat[t]._shard_body(node_caps[t])
                   for t in feat_types}
    lab_body = self._label_store._shard_body(
        label_cap if label_cap is not None else node_caps[t_in])
    d = sampler._dev
    gsh = {et: sampler.graph_shards(et) for et in sampler.graph.etypes}
    fdevs = {t: self._feat[t].device_arrays() for t in feat_types}
    ldev = self._label_store.device_arrays()
    shard_keys = self._label_store.SHARD_KEYS
    repl_keys = self._label_store.REPL_KEYS
    table_args = self._label_store.table_args
    shard_tree = dict(
        g=gsh,
        f={t: {k: fdevs[t][k] for k in shard_keys} for t in feat_types},
        l={k: ldev[k] for k in shard_keys})
    repl_tree = dict(
        pb=dict(d['#pb']),
        f={t: {k: fdevs[t][k] for k in repl_keys} for t in feat_types},
        l={k: ldev[k] for k in repl_keys})

    def body(views, repl, stats_rows, seeds, smask, key):
      res, _ = sampler._hetero_engine(views['g'], repl['pb'],
                                      {t_in: (seeds, smask)}, key, plan)
      x, new_rows = {}, {}
      for t in feat_types:
        ids = res['node'][t]
        x[t], new_rows[t] = feat_bodies[t](
            *table_args(views['f'][t], repl['f'][t]), stats_rows[t], ids,
            ids >= 0)
      ids = res['node'][t_in]
      lab_ids = ids[:label_cap] if label_cap is not None else ids
      y, _ = lab_body(*table_args(views['l'], repl['l']),
                      jnp.zeros((4,), jnp.int32), lab_ids, lab_ids >= 0)
      ei = {et: jnp.stack([res['row'][et], res['col'][et]])
            for et in res['row']}
      batch = dict(x=x, edge_index=ei, edge_mask=res['edge_mask'],
                   y=y[:, 0],
                   num_seed_nodes=res['num_sampled_nodes'][t_in][0])
      # the typed engine counts no exchange rows: an empty [0] row
      return batch, res['overflow'], new_rows, jnp.zeros((0,), jnp.int32)

    return shard_tree, repl_tree, body

  # ------------------------------------------------- per-step reference

  def _build_step_fn(self):
    """The per-step data-parallel train program (ONE dispatch per
    optimizer update): shard_map over the mesh, per-shard batch views,
    pmean'd grads, replicated state in/out."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..utils.compat import shard_map
    ax = self._axes
    dp = self._dp_step_body

    def body(state, x, ei, em, y, nseed):
      view = lambda t: jax.tree.map(lambda a: a[0], t)
      batch = dict(x=view(x), edge_index=view(ei), edge_mask=view(em),
                   y=y[0], num_seed_nodes=nseed[0])
      return dp(state, batch)

    fn = shard_map(
        body, mesh=self.mesh,
        in_specs=(P(), P(ax), P(ax), P(ax), P(ax), P(ax)),
        out_specs=(P(), P(), P()), check_replication=False)
    return jax.jit(fn)

  def train_step(self, state, batch):
    """One data-parallel optimizer update from a collocated dist batch
    (the loader's stacked Data/HeteroData). Returns
    ``(state, loss, acc)`` — loss/acc replicated device scalars."""
    import jax.numpy as jnp

    from ..metrics import programs
    from ..utils.trace import record_dispatch
    if self._step_fn is None:
      self._step_fn = programs.instrument(self._build_step_fn(),
                                          'dist_train_step')
    if self.is_hetero:
      y = batch.y[self._input_type]
      nseed = jnp.asarray(batch.num_sampled_nodes[self._input_type])[:, 0]
    else:
      y = batch.y
      nseed = jnp.asarray(batch.num_sampled_nodes)[:, 0]
    record_dispatch('dist_train_step')
    return self._step_fn(state, batch.x, batch.edge_index,
                         batch.edge_mask, y, nseed)

  def run_epoch_steps(self, state, max_steps: Optional[int] = None):
    """The PER-STEP reference epoch: iterate the collocated loader
    (sample + collate dispatches per batch) and apply the data-parallel
    step per batch — the loop the scanned epoch must replay
    bit-identically (shuffle=False) and the A/B baseline for the
    dispatch-count story. Returns (state, losses) with ``losses`` a
    list of replicated device scalars."""
    losses = []
    for batch in self.loader:
      state, loss, _ = self.train_step(state, batch)
      losses.append(loss)
      if max_steps is not None and len(losses) >= max_steps:
        break
    return state, losses
