"""TieredDistScanTrainer: device oversubscription THROUGH the shard
exchange.

``DistScanTrainer`` runs a collocated-mesh epoch as ceil(steps/K)+2
dispatches, but every shard's HBM still holds its FULL feature
partition — the in-program all_to_all must be able to answer any
remote request. This trainer erases that boundary (ROADMAP item 2;
PyTorch-Direct, arxiv 2101.07956, and GPU-initiated direct storage,
arxiv 2306.16384, are the GPU-world exemplars — this is the multi-host
TPU instance):

* **Hot prefix per shard.** Each shard's HBM holds only positions
  ``[0, H)`` of its sorted partition table
  (``TieredDistFeature.dist_scan_tables``) plus a double-buffered
  pow2-padded exchange slab; the rest of the partition lives in the
  store's host/disk tiers.
* **Miss-exchange program.** The epoch prologue extends the seed
  program with an id-only replay of the distributed sampler over every
  step — the SAME ``split(fold_in(base_key, count), P)`` keys the chunk
  programs derive, so the draws are bit-identical by the PR 4 replay
  contract — still ONE ``dist_epoch_seeds`` dispatch. The fetched
  [P, steps, node_cap] request matrix is the prologue's one explicit
  ``jax.device_get``; ``planner.plan_exchange`` turns it into the exact
  per-chunk program: which POSITIONS of each shard's table its peers
  will request during each chunk, beyond the replicated hot cache and
  the HBM hot prefix.
* **Chunk-boundary slab staging.** While chunk ``c`` trains, a
  ``DistChunkStager`` worker gathers chunk ``c+1``'s planned positions
  from the per-partition tiers into a [P, cap] / [P, cap, F] host slab
  (pow2 ``cap`` = the chunk's max per-shard count — one executable per
  (chunk length, slab cap)); the dispatch thread device_puts it sharded
  over the mesh and dispatches the chunk.
* **In-program slab-backed exchange.** The chunk program's feature
  lookup is ``DistFeature._shard_body(slab=True)``: a remote request
  resolves its position exactly as before, then gathers ``hot[pos]``
  for positions < H and ``slab[searchsorted(slab_pos, pos)]`` for the
  rest — under the exact plan every staged bytes equals the all-HBM
  row, so LOSSES AND PARAMS ARE BIT-IDENTICAL to ``DistScanTrainer``
  at the unchanged ceil(steps/K)+2 dispatch budget.
* **Degradation, never corruption.** A failed/slow staging worker
  degrades to a synchronous gather of the same planned positions
  (``storage.prefetch_miss``); the chaos suite completes the epoch
  bit-identically with a ``storage.dist_stage`` fault armed
  (docs/failure_model.md).

Scope: collocated meshes (flat or 2-axis hierarchical — the
slab-backed lookup rides both exchange forms), homogeneous or
heterogeneous. Hetero stores are a ``{ntype: TieredDistFeature}``
dict whose per-ntype closed shapes come from the stream's CapacityPlan
(docs/capacity_plans.md): the prologue replays the typed engine
id-only, plans ONE exchange per feature-bearing ntype, and each chunk
stages one slab per ntype — the homo path is the single-ntype
degenerate case of the same machinery. Labels stay a full (small)
DistFeature. Single-process meshes: the prologue fetch and the stager
read the whole [P, ...] request matrix / tier set locally.
"""
from typing import Optional

import numpy as np

from .. import metrics
from ..loader.scan_epoch import DistScanTrainer
from ..sampler import CapacityPlanError
from ..utils.faults import fault_point
from ..utils.trace import record_dispatch
from . import planner
from .dist import TieredDistFeature
from .staging import INT32_MAX, ChunkStager, pow2_slab_cap


class DistChunkStager(ChunkStager):
  """ChunkStager whose plan rows are ENCODED ``p * n_max + position``
  addresses (planner.ExchangePlan) and whose slabs come back in the
  [P, cap] per-shard layout the shard_map chunk program consumes.
  Pad slots carry INT32_MAX positions (never match a searchsorted);
  per-shard position lists stay sorted because the encoded plan is."""

  def _stage_fault(self):
    # the dist pipeline's own registered chaos site — worker-only, so
    # take()'s synchronous fallback still gathers cleanly
    fault_point('storage.dist_stage')

  def _gather(self, enc: np.ndarray):
    store = self.store
    nparts, n_max = store.num_partitions, store.n_max
    enc = np.asarray(enc, np.int64)
    owners = enc // n_max
    pos = enc % n_max
    counts = (np.bincount(owners, minlength=nparts) if enc.size
              else np.zeros((nparts,), np.int64))
    cap = pow2_slab_cap(int(counts.max()) if enc.size else 1)
    ids = np.full((nparts, cap), INT32_MAX, np.int32)
    rows = np.zeros((nparts, cap, store.feature_dim),
                    store.storage_dtype)
    for p in range(nparts):
      kp = int(counts[p])
      if kp:
        m = owners == p
        ids[p, :kp] = pos[m].astype(np.int32)
        rows[p, :kp] = store.gather_positions(p, pos[m])
    metrics.inc('storage.dist_staged_rows', int(enc.shape[0]))
    return ids, rows


class TieredDistScanTrainer(DistScanTrainer):
  """DistScanTrainer over a ``TieredDistFeature`` whose HBM holds only
  each shard's hot prefix + the in-flight exchange slabs (module
  docstring).

  Args (beyond DistScanTrainer's):
    max_ahead: staged chunks in flight (2 = double buffer).
    stage_timeout_s: how long a chunk boundary waits for its slab
      before degrading to a synchronous gather.
  """

  _NAME = 'TieredDistScanTrainer'

  def __init__(self, loader, model, tx, num_classes: int,
               chunk_size: int = 32,
               seed_labels_only: Optional[bool] = None,
               perm_seed: Optional[int] = None, max_ahead: int = 2,
               stage_timeout_s: float = 30.0):
    sampler = getattr(loader, 'sampler', None)
    store = getattr(sampler, 'dist_feature', None)
    # homo or hetero, ONE store contract: every feature store the chunk
    # program reads must be a TieredDistFeature with a hot prefix — the
    # per-ntype slab capacities of the hetero exchange (and the single
    # slab of the homo degenerate plan) come from these stores' sorted
    # row tables (docs/capacity_plans.md)
    stores = store if isinstance(store, dict) else \
        ({None: store} if store is not None else {})
    bad = sorted(f'{t}:{type(s).__name__}' for t, s in stores.items()
                 if not isinstance(s, TieredDistFeature))
    if not stores or bad:
      raise CapacityPlanError(
          self._NAME,
          'the feature store set carries no per-ntype slab capacities '
          f'(non-tiered stores: {bad or "<empty>"})',
          hint='build every feature store as storage.TieredDistFeature('
               'hot_prefix_rows >= 1) so the exchange planner can close '
               "each ntype's slab shapes; all-HBM DistFeature "
               'partitions keep loader.DistScanTrainer')
    low = sorted(str(t) for t, s in stores.items()
                 if s.hot_prefix_rows < 1)
    if low:
      raise CapacityPlanError(
          self._NAME,
          f'stores {low} declare no hot prefix (hot_prefix_rows < 1)',
          hint='the chunk program clamps pad positions into the hot '
               'prefix — pass hot_prefix_rows >= 1 at store '
               'construction')
    # spilled partitions are named part_NNN inside spill_dir: two
    # per-ntype stores sharing a directory overwrite each other's rows
    # at construction and every later gather silently reads the LAST
    # writer's features — a corruption, not a crash, so refuse loudly
    import os as _os
    dirs = {}
    for t, s in stores.items():
      d = getattr(s, '_spill_dir', None)
      if d is not None:
        dirs.setdefault(_os.path.realpath(d), []).append(str(t))
    clash = sorted((d, sorted(ts)) for d, ts in dirs.items()
                   if len(ts) > 1)
    if clash:
      raise CapacityPlanError(
          self._NAME,
          'per-ntype stores share a spill_dir — their part_NNN spill '
          f'files overwrite each other ({clash})',
          hint='give every ntype its own spill_dir (e.g. '
               'os.path.join(root, ntype)) so each store keeps its own '
               'sorted-row tables')
    self._store = store
    super().__init__(loader, model, tx, num_classes, chunk_size,
                     seed_labels_only, perm_seed)
    if self.is_hetero:
      # one staging pipeline per sampled feature-bearing ntype — the
      # CapacityPlan's node_caps pick the set; each ntype's slab caps
      # close independently over its own plan
      self._stagers = {t: DistChunkStager(self._feat[t],
                                          max_ahead=max_ahead,
                                          timeout_s=stage_timeout_s)
                       for t in self._feat_types}
      self._stager = None
    else:
      self._stager = DistChunkStager(store, max_ahead=max_ahead,
                                     timeout_s=stage_timeout_s)
      self._stagers = None
    self.last_plan = None   # ExchangePlan(s) of the most recent epoch

  # ------------------------------------------------------------- programs

  def _make_sample_collate(self):
    """The base sample+collate body with the SLAB-BACKED feature
    lookup: ``views['f']`` carries (feat_ids, hot) instead of the full
    partition, and the body takes the chunk's per-shard slab views as
    two extra trailing arguments (per-ntype dicts on hetero meshes).
    The label store stays a full (small) DistFeature."""
    import jax.numpy as jnp
    if self.is_hetero:
      return self._make_hetero_sample_collate()
    sampler = self._sampler
    b = self._batch_size
    label_cap = self._label_cap

    from ..distributed.dist_neighbor_sampler import _homo_hop_loop
    fanouts = tuple(sampler.num_neighbors)
    caps = sampler._capacities(b)
    node_cap = sampler._node_cap(caps)
    dedup = sampler.dedup
    weighted = sampler._weighted_for()
    bucket_frac = sampler.bucket_frac
    ax, sizes, nparts = self._axes, self._axis_sizes, self._nparts
    feat_body = self._feat._shard_body(node_cap, slab=True)
    lab_body = self._label_store._shard_body(
        label_cap if label_cap is not None else node_cap)
    d = sampler._dev
    gsh = sampler.graph_shards()
    index = sampler.row_index_statics()
    # hot-prefix tables only — the full [P, n_max, F] partition table is
    # never uploaded on this path (device_arrays stays the per-step
    # loaders' contract)
    fdev = self._store.dist_scan_tables()
    ldev = self._label_store.device_arrays()
    shard_keys = self._label_store.SHARD_KEYS
    repl_keys = self._label_store.REPL_KEYS
    table_args = self._label_store.table_args
    shard_tree = dict(
        g=gsh,
        f={k: fdev[k] for k in ('feat_ids', 'feat_starts', 'hot')},
        l={k: ldev[k] for k in shard_keys})
    repl_tree = dict(
        pb=d['node_pb'],
        f={k: fdev[k] for k in repl_keys},
        l={k: ldev[k] for k in repl_keys})

    def body(views, repl, stats_rows, seeds, smask, key, slab_pos,
             slab_rows):
      res = _homo_hop_loop(views['g'], repl['pb'], seeds, smask, key,
                           fanouts, caps, node_cap, nparts, False,
                           weighted, dedup=dedup,
                           bucket_frac=bucket_frac, axes=ax,
                           axis_sizes=sizes, index=index)
      ids = res['node']
      fv = views['f']
      x, srow = feat_body(
          *table_args(fv, repl['f'], (fv['hot'], slab_pos, slab_rows)),
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
    """Typed slab-backed collate: the base hetero body
    (loader/pipeline.py _make_hetero_sample_collate) with every
    per-ntype feature lookup resolved against (hot prefix + that
    ntype's staged slab) instead of the full partition table. The
    CapacityPlan's per-ntype ``node_caps`` size both the lookup bodies
    and the prologue's replayed request matrices, so planned and
    served can never disagree per type."""
    import jax.numpy as jnp
    sampler = self._sampler
    b = self._batch_size
    label_cap = self._label_cap
    t_in = self._input_type
    plan = sampler._hetero_plan({t_in: b})
    _, _, node_caps = plan
    feat_types = [t for t in sampler.graph.ntypes
                  if node_caps.get(t, 0) > 0 and t in self._feat]
    self._feat_types = feat_types
    self._h_plan = plan     # the typed engine plan the seed fn replays
    feat_bodies = {t: self._feat[t]._shard_body(node_caps[t], slab=True)
                   for t in feat_types}
    lab_body = self._label_store._shard_body(
        label_cap if label_cap is not None else node_caps[t_in])
    d = sampler._dev
    gsh = {et: sampler.graph_shards(et) for et in sampler.graph.etypes}
    # hot-prefix tables only, per ntype — no full [P, n_max, F] uploads
    fdevs = {t: self._feat[t].dist_scan_tables() for t in feat_types}
    ldev = self._label_store.device_arrays()
    shard_keys = self._label_store.SHARD_KEYS
    repl_keys = self._label_store.REPL_KEYS
    table_args = self._label_store.table_args
    shard_tree = dict(
        g=gsh,
        f={t: {k: fdevs[t][k] for k in ('feat_ids', 'feat_starts', 'hot')}
           for t in feat_types},
        l={k: ldev[k] for k in shard_keys})
    repl_tree = dict(
        pb=dict(d['#pb']),
        f={t: {k: fdevs[t][k] for k in repl_keys} for t in feat_types},
        l={k: ldev[k] for k in repl_keys})

    def body(views, repl, stats_rows, seeds, smask, key, slab_pos,
             slab_rows):
      res, _ = sampler._hetero_engine(views['g'], repl['pb'],
                                      {t_in: (seeds, smask)}, key, plan)
      x, new_rows = {}, {}
      for t in feat_types:
        ids = res['node'][t]
        fv = views['f'][t]
        x[t], new_rows[t] = feat_bodies[t](
            *table_args(fv, repl['f'][t],
                        (fv['hot'], slab_pos[t], slab_rows[t])),
            stats_rows[t], ids, ids >= 0)
      ids = res['node'][t_in]
      lab_ids = ids[:label_cap] if label_cap is not None else ids
      y, _ = lab_body(*table_args(views['l'], repl['l']),
                      jnp.zeros((4,), jnp.int32), lab_ids, lab_ids >= 0)
      ei = {et: jnp.stack([res['row'][et], res['col'][et]])
            for et in res['row']}
      batch = dict(x=x, edge_index=ei, edge_mask=res['edge_mask'],
                   y=y[:, 0],
                   num_seed_nodes=res['num_sampled_nodes'][t_in][0])
      return batch, res['overflow'], new_rows, jnp.zeros((0,), jnp.int32)

    return shard_tree, repl_tree, body

  def _build_seed_fn(self):
    """The prologue PLAN program: the base seed/permutation math PLUS
    an id-only replay of the distributed sampler over every step inside
    one shard_map — emitting the [P, steps, node_cap] request matrix
    alongside the sharded seed matrices. One dispatch, fetched once;
    the keys are exactly the chunk programs'
    ``split(fold_in(base_key, count), P)[shard]`` stream, so the
    replayed requests ARE the chunk requests, bit for bit."""
    if self.is_hetero:
      return self._build_hetero_seed_fn()
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..distributed.dist_neighbor_sampler import _homo_hop_loop
    from ..utils.compat import shard_map
    sampler = self._sampler
    batch = self._batch_size
    nparts = self._nparts
    shuffle = self.loader.shuffle
    fanouts = tuple(sampler.num_neighbors)
    caps = sampler._capacities(batch)
    node_cap = sampler._node_cap(caps)
    dedup = sampler.dedup
    weighted = sampler._weighted_for()
    bucket_frac = sampler.bucket_frac
    ax, sizes = self._axes, self._axis_sizes
    index = sampler.row_index_statics()
    mesh = self.mesh
    gspec = jax.tree.map(lambda _: P(ax), self._shard_tree['g'])

    def plan(gsh, pb, seeds, key, base_key, count0, steps):
      def body(gsh_s, pb_s, seeds_s, key_s, base_key_s, count0_s):
        gviews = jax.tree.map(lambda a: a[0], gsh_s)
        my = jnp.int32(0)
        for a in ax:
          my = my * mesh.shape[a] + lax.axis_index(a)
        n = seeds_s.shape[0]
        # the SAME permutation math as DistScanTrainer._build_seed_fn
        # (replicated computation per shard): arange + cyclic ragged
        # tail, or the on-device epoch permutation
        order = (jax.random.permutation(key_s, n) if shuffle
                 else jnp.arange(n, dtype=jnp.int32))
        total = steps * nparts * batch
        if total <= n:
          ext = order[:total]
          maskf = jnp.ones((total,), bool)
        else:
          pad = order[jnp.arange(total - n, dtype=jnp.int32) % n]
          ext = jnp.concatenate([order, pad])
          maskf = jnp.arange(total) < n
        seed_all = seeds_s[ext].reshape(steps, nparts, batch)
        mask_all = maskf.reshape(steps, nparts, batch)
        seeds_my = jnp.take(seed_all, my, axis=1)    # [steps, B]
        mask_my = jnp.take(mask_all, my, axis=1)
        counts = count0_s + lax.iota(jnp.int32, steps)

        def step(carry, xs):
          s, m, cnt = xs
          keys = jax.random.split(
              jax.random.fold_in(base_key_s, cnt), nparts)
          res = _homo_hop_loop(gviews, pb_s, s, m, keys[my], fanouts,
                               caps, node_cap, nparts, False, weighted,
                               dedup=dedup, bucket_frac=bucket_frac,
                               axes=ax, axis_sizes=sizes, index=index)
          return carry, res['node']

        _, rows = lax.scan(step, 0, (seeds_my, mask_my, counts))
        return seeds_my[None], mask_my[None], rows[None]

      fn = shard_map(body, mesh=mesh,
                     in_specs=(gspec, P(), P(), P(), P(), P()),
                     out_specs=(P(ax), P(ax), P(ax)),
                     check_replication=False)
      return fn(gsh, pb, seeds, key, base_key, count0)

    return jax.jit(plan, static_argnums=(6,))

  def _build_hetero_seed_fn(self):
    """Typed prologue PLAN program: the same permutation math plus an
    id-only replay of ``_hetero_engine`` over every step, emitting ONE
    per-ntype request matrix dict ``{ntype: [P, steps, node_caps[t]]}``
    — the CapacityPlan's per-ntype shapes, closed at trace time. Still
    one ``dist_epoch_seeds`` dispatch; the keys are exactly the typed
    chunk programs' ``split(fold_in(base_key, count), P)[shard]``
    stream."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..utils.compat import shard_map
    sampler = self._sampler
    batch = self._batch_size
    nparts = self._nparts
    shuffle = self.loader.shuffle
    t_in = self._input_type
    eplan = self._h_plan          # set by _make_hetero_sample_collate
    feat_types = list(self._feat_types)
    ax = self._axes
    mesh = self.mesh
    gspec = jax.tree.map(lambda _: P(ax), self._shard_tree['g'])

    def plan(gsh, pb, seeds, key, base_key, count0, steps):
      def body(gsh_s, pb_s, seeds_s, key_s, base_key_s, count0_s):
        gviews = jax.tree.map(lambda a: a[0], gsh_s)
        my = jnp.int32(0)
        for a in ax:
          my = my * mesh.shape[a] + lax.axis_index(a)
        n = seeds_s.shape[0]
        order = (jax.random.permutation(key_s, n) if shuffle
                 else jnp.arange(n, dtype=jnp.int32))
        total = steps * nparts * batch
        if total <= n:
          ext = order[:total]
          maskf = jnp.ones((total,), bool)
        else:
          pad = order[jnp.arange(total - n, dtype=jnp.int32) % n]
          ext = jnp.concatenate([order, pad])
          maskf = jnp.arange(total) < n
        seed_all = seeds_s[ext].reshape(steps, nparts, batch)
        mask_all = maskf.reshape(steps, nparts, batch)
        seeds_my = jnp.take(seed_all, my, axis=1)    # [steps, B]
        mask_my = jnp.take(mask_all, my, axis=1)
        counts = count0_s + lax.iota(jnp.int32, steps)

        def step(carry, xs):
          s, m, cnt = xs
          keys = jax.random.split(
              jax.random.fold_in(base_key_s, cnt), nparts)
          res, _ = sampler._hetero_engine(gviews, pb_s,
                                          {t_in: (s, m)}, keys[my],
                                          eplan)
          return carry, {t: res['node'][t] for t in feat_types}

        _, rows = lax.scan(step, 0, (seeds_my, mask_my, counts))
        return (seeds_my[None], mask_my[None],
                {t: rows[t][None] for t in feat_types})

      fn = shard_map(body, mesh=mesh,
                     in_specs=(gspec, P(), P(), P(), P(), P()),
                     out_specs=(P(ax), P(ax),
                                {t: P(ax) for t in feat_types}),
                     check_replication=False)
      return fn(gsh, pb, seeds, key, base_key, count0)

    return jax.jit(plan, static_argnums=(6,))

  def _chunk_fn_for(self, k: int, cap: Optional[int] = None):
    """The slab-aware scanned K-step shard_map program, keyed by
    (chunk length, slab cap) — pow2 caps keep the executable set
    closed. Arg order extends the base program's with the two slab
    arrays at the END, so the donation set (stats + train state +
    overflow) is unchanged; slabs are fresh per chunk and never
    donated."""
    if cap is None:   # the base signature — unreachable via our seam
      raise TypeError(f'{self._NAME}._chunk_fn_for needs the slab cap')
    ck = (k, cap)
    if ck in self._chunk_fns:
      return self._chunk_fns[ck]
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..metrics import programs
    from ..utils.compat import shard_map
    ax = self._axes
    mesh = self.mesh
    nparts = self._nparts
    sc_body = self._sc_body
    dp = self._dp_step_body

    def body(shard_tree, repl_tree, stats, params, opt_state, stepc,
             ovf, seed_mat, mask_mat, base_key, count0, start, slab_pos,
             slab_rows):
      views = jax.tree.map(lambda a: a[0], shard_tree)
      stats_rows = jax.tree.map(lambda a: a[0], stats)
      sp_v = jax.tree.map(lambda a: a[0], slab_pos)
      sr_v = jax.tree.map(lambda a: a[0], slab_rows)
      seeds_k = lax.dynamic_slice_in_dim(seed_mat[0], start, k, 0)
      masks_k = lax.dynamic_slice_in_dim(mask_mat[0], start, k, 0)
      counts_k = count0 + start + lax.iota(jnp.int32, k)
      my = jnp.int32(0)
      for a in ax:
        my = my * mesh.shape[a] + lax.axis_index(a)

      def step(carry, xs):
        params, opt_state, stepc, ovf, srows = carry
        seeds, smask, count = xs
        keys = jax.random.split(jax.random.fold_in(base_key, count),
                                nparts)
        batch, overflow, srows, sent = sc_body(
            views, repl_tree, srows, seeds, smask, keys[my], sp_v, sr_v)
        state, loss, acc = dp(
            self._train_state_cls(params, opt_state, stepc), batch)
        return (state.params, state.opt_state, state.step,
                ovf | overflow, srows), (loss, acc, sent)

      (params, opt_state, stepc, ovf, srows), (losses, accs, sent) = \
          lax.scan(step, (params, opt_state, stepc, ovf, stats_rows),
                   (seeds_k, masks_k, counts_k))
      return (params, opt_state, stepc, ovf,
              jax.tree.map(lambda a: a[None], srows), losses, accs,
              sent[None])

    sh = jax.tree.map(lambda _: P(ax), self._shard_tree)
    rp = jax.tree.map(lambda _: P(), self._repl_tree)
    stats_spec = (P(ax) if not self.is_hetero
                  else {t: P(ax) for t in self._feat_types})
    slab_spec = (P(ax) if not self.is_hetero
                 else {t: P(ax) for t in self._feat_types})
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(sh, rp, stats_spec, P(), P(), P(), P(), P(ax), P(ax),
                  P(), P(), P(), slab_spec, slab_spec),
        out_specs=(P(), P(), P(), P(), stats_spec, P(), P(), P(ax)),
        check_replication=False)
    jfn = programs.instrument(
        jax.jit(fn, donate_argnums=(2, 3, 4, 5, 6)), 'dist_scan_chunk')
    self._chunk_fns[ck] = jfn
    return jfn

  # ------------------------------------------------ exchange-aware seams

  def _epoch_prologue(self, perm_key, full_steps, steps, start_step,
                      base_key, count0):
    """One plan dispatch + the prologue's ONE explicit fetch: the
    replayed request matrix (per-ntype matrices on hetero meshes)
    becomes the per-chunk miss-exchange program — one ExchangePlan per
    feature-bearing ntype — and staging starts at the resume chunk
    (consumed chunks never stage again)."""
    import jax
    record_dispatch('dist_epoch_seeds')
    seed_mat, mask_mat, rows_mat = self._seed_fn(
        self._shard_tree['g'], self._repl_tree['pb'], self._seeds_dev,
        perm_key, base_key, count0, full_steps)
    # explicit device_get — strict_guards rejects implicit transfers only
    rows_host = jax.device_get(rows_mat)
    start_chunk = start_step // self.chunk_size
    if self.is_hetero:
      plans = {}
      for t in self._feat_types:
        st = self._feat[t]
        plans[t] = planner.plan_exchange(
            np.asarray(rows_host[t])[:, :steps], self.chunk_size,
            st.feature_pb, st.feat_ids, st.hot_prefix_rows,
            cache_ids=st.cache_ids)
        self._stagers[t].begin_epoch(plans[t].chunk_rows,
                                     start_chunk=start_chunk)
      self.last_plan = plans
    else:
      plan = planner.plan_exchange(
          np.asarray(rows_host)[:, :steps], self.chunk_size,
          self._store.feature_pb, self._store.feat_ids,
          self._store.hot_prefix_rows, cache_ids=self._store.cache_ids)
      self.last_plan = plan
      self._stager.begin_epoch(plan.chunk_rows, start_chunk=start_chunk)
    return seed_mat, mask_mat

  def _dispatch_chunk(self, c, k, stats, params, opt_state, stepc, ovf,
                      seed_mat, mask_mat, base_key, count0, start_dev):
    """Take chunk ``c``'s staged slab(s) (or degrade to a synchronous
    gather of the same planned positions), upload them sharded over the
    mesh (explicit device_puts — the strict region stays clean), and
    dispatch the (k, caps) program. Hetero chunks stage one slab per
    feature-bearing ntype; the executable is keyed by the per-ntype
    pow2 cap tuple so the compiled set stays closed. The ack frees the
    host ring slots; the device copies belong to the in-flight
    program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..utils import global_device_put
    sharded = NamedSharding(self.mesh, P(self._axes))
    if self.is_hetero:
      slab_np = {t: self._stagers[t].take(c) for t in self._feat_types}
      slab_pos = {t: global_device_put(v[0], sharded)
                  for t, v in slab_np.items()}
      slab_rows = {t: global_device_put(v[1], sharded)
                   for t, v in slab_np.items()}
      cap = tuple(int(slab_np[t][0].shape[1]) for t in self._feat_types)
    else:
      slab_pos_np, slab_rows_np = self._stager.take(c)
      slab_pos = global_device_put(slab_pos_np, sharded)
      slab_rows = global_device_put(slab_rows_np, sharded)
      cap = int(slab_pos_np.shape[1])
    record_dispatch('dist_scan_chunk')
    out = self._chunk_fn_for(k, cap)(
        self._shard_tree, self._repl_tree, stats, params, opt_state,
        stepc, ovf, seed_mat, mask_mat, base_key, count0, start_dev,
        slab_pos, slab_rows)
    if self.is_hetero:
      for t in self._feat_types:
        self._stagers[t].ack(c)
    else:
      self._stager.ack(c)
    return out

  # ---------------------------------------------------------- lifecycle

  def _flight_config(self) -> dict:
    cfg = super()._flight_config()
    if self.is_hetero:
      cfg.update(
          hot_prefix_rows={t: self._feat[t].hot_prefix_rows
                           for t in self._feat_types},
          n_max={t: self._feat[t].n_max for t in self._feat_types})
    else:
      cfg.update(hot_prefix_rows=self._store.hot_prefix_rows,
                 n_max=self._store.n_max)
    return cfg

  def _recovery_capture(self, carry):
    """DistScanTrainer's capture plus the staging-ring watermarks
    (diagnostic — a resume re-plans and re-stages)."""
    meta, dev = super()._recovery_capture(carry)
    meta['staging'] = ({t: self._stagers[t].watermarks()
                        for t in self._feat_types}
                       if self.is_hetero else self._stager.watermarks())
    return meta, dev

  def close(self):
    """Stop the staging worker thread(s)."""
    if self._stagers is not None:
      for st in self._stagers.values():
        st.close()
    if self._stager is not None:
      self._stager.close()
