"""Family ``mesh_node``: supervised node classification on one homogeneous
graph PARTITIONED over the chips of a host — one partition a chip, graph
and rows resident in HBM, a share of the rows (hottest by in-degree)
replicated on every chip — through ``DistDataset.from_device_shards`` ->
``estimate_dist_frontier_caps`` -> ``DistNeighborLoader(dedup='merge',
frontier_caps)`` -> a ``merge_dense`` model under ``DistScanTrainer``.

The dataset is the CONFIGURATION's and is made where it lives
(``perfbench/datagen_mesh_node.py``): every shard's CSR, rows and labels
on its own device, so the host never holds an array of N x F or E
elements. What the host does get, and only once ``correct`` is being
decided (after the window): each shard's ``indptr`` and ``indices`` as the
generator made them, one shard at a time and kept apart — the arrays the
exact numbers are counted against; rows and labels are regenerated from
node ids (they are functions of ``graph_seed`` and the id alone).

A *batch* here is one step's P shard batches: a dict of host arrays with
a leading ``[P, ...]`` axis, as ``executors/mesh_scan.py`` replays them.
"""
import time

import numpy as np

# what this family needs of the program, asked for before any data is
# made: a program that cannot take shards already on their devices has
# neither name, and a run on it ends here, within seconds
from graphlearn_tpu.distributed import DistDataset
from graphlearn_tpu.sampler import estimate_dist_frontier_caps

from perfbench import datagen_mesh_node as datagen
from perfbench import flops_mesh_node, reference_mesh_node as reference

EXACT = ('bad_edges', 'fanout_misses', 'dup_nodes', 'bad_rows', 'overflow',
         'exchange_overflow', 'seed_overlap')


class Cell:
  """What a configuration builds once per process. ``run.py`` and
  ``control.py`` call ``shapes``, ``exact_numbers`` and ``follower``; the
  executor ``make_loader``, ``make_model``, ``make_state``,
  ``valid_counts``, ``feature_counters`` and reads ``batch``, ``parts``,
  ``num_classes``, ``steps_per_call``; the readers ``step_flops``,
  ``collate_bytes`` and ``exchange_bytes``."""

  def __init__(self, cfg, traffic, log):
    import jax
    from jax.sharding import Mesh

    from graphlearn_tpu.models import train as train_lib
    self.cfg, self.traffic = cfg, traffic
    d, m, fs = cfg['dataset'], cfg['model'], cfg['feature_store']
    if m.get('matmul_precision'):
      jax.config.update('jax_default_matmul_precision',
                        m['matmul_precision'])
    self.parts = int(cfg['partitions'])
    devs = jax.devices()
    if len(devs) < self.parts:
      raise SystemExit(f'mesh_node: {self.parts} partitions need '
                       f'{self.parts} devices; jax found {len(devs)}')
    self.mesh = Mesh(np.array(devs[:self.parts]), ('g',))
    self.num_nodes, self.num_classes = d['num_nodes'], d['num_classes']
    self.graph_seed = cfg['graph_seed']
    data = datagen.generate(
        self.mesh, d['num_nodes'], d['num_directed_edges'],
        d['num_classes'], d['feat_dim'], d['p_intra'], d['feat_snr'],
        d['num_train'], cfg['graph_seed'], d['powerlaw_dmax'], log=log)
    self.centres = data['centres']
    self.train_idx = data['train_idx']
    # the generator's CSR, still on the devices: fetched shard by shard
    # when the exact numbers are counted (``_host_csr``)
    self._csr_dev = (data['graph']['indptr'], data['graph']['indices'])
    self._csr = None
    t0 = time.perf_counter()
    assert fs['hotness'] == 'in_degree' and fs['wire_dtype'] == 'float32' \
        and fs['miss_dedup'], fs
    self.dataset = DistDataset.from_device_shards(
        self.mesh, data['node_pb'], data['graph'], data['features'],
        labels=data['labels'], split_ratio=fs['split_ratio'],
        hotness=data['in_degree'], bucket_frac=fs['bucket_frac'])
    jax.block_until_ready(
        self.dataset.node_features.device_arrays()['cache_feats'])
    del data
    log('place_s', time.perf_counter() - t0)
    # what stays on the fullest chip once the generator's own arrays are
    # gone (0 where the backend keeps no count): ``hbm_peak_gb`` reads a
    # peak, and set-up's is above this by the in-degree vector and the
    # labels as the generator handed them over
    self.resident_bytes = max(
        (d.memory_stats() or {}).get('bytes_in_use', 0)
        for d in jax.local_devices())
    t0 = time.perf_counter()
    self.fanout = list(m['fanout'])
    self.batch = int(m['batch_size'])           # seeds a PARTITION
    cal = traffic['calibration']
    self.caps = [int(c) for c in estimate_dist_frontier_caps(
        self.dataset.graph, self.mesh, self.fanout, self.batch,
        input_nodes=self.train_idx, num_probes=cal['num_probes'],
        slack=cal['slack'], seed=cal['seed'])]
    log('calibrate_s', time.perf_counter() - t0)
    self.node_offsets, self.edge_offsets = train_lib.merge_hop_offsets(
        self.batch, self.fanout, None, self.caps)
    self.model_desc = dict(
        kind=m['kind'], in_dim=d['feat_dim'], hidden=m['hidden'],
        out_dim=d['num_classes'], layers=len(self.fanout), heads=1)
    if m['kind'] != 'sage':
      raise ValueError(f'mesh_node: unknown model kind {m["kind"]!r}')
    self.lr = float(m['lr'])
    self.feat_dim = d['feat_dim']
    self.steps_per_call = int(cfg['steps_per_call'])

  # -------------------------------------------------- for the executor

  def make_model(self, dtype=None):
    from graphlearn_tpu.models import GraphSAGE
    md = self.model_desc
    return GraphSAGE(
        hidden_dim=md['hidden'], out_dim=md['out_dim'],
        num_layers=md['layers'], hop_node_offsets=self.node_offsets,
        hop_edge_offsets=self.edge_offsets, merge_dense=True,
        fanouts=tuple(self.fanout), dtype=dtype)

  def make_loader(self, seed):
    import graphlearn_tpu as glt
    fs = self.cfg['feature_store']
    return glt.distributed.DistNeighborLoader(
        self.dataset, self.fanout, self.train_idx, batch_size=self.batch,
        shuffle=bool(self.traffic['shuffle']), drop_last=True,
        seed=int(seed) % (2 ** 31 - 1), mesh=self.mesh,
        dedup=self.traffic['dedup'], frontier_caps=self.caps,
        bucket_frac=fs['bucket_frac'],
        seed_labels_only=bool(fs['seed_labels_only']))

  def make_state(self, model, seed):
    """The program's TrainState around the harness's own weights, after
    checking that the program's model would have made the same tree."""
    import jax
    import jax.numpy as jnp
    import optax
    from graphlearn_tpu.models import train as train_lib
    params = reference.init_params(self.model_desc, seed)
    spec = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((self.node_offsets[-1], self.feat_dim),
                             jnp.float32),
        jax.ShapeDtypeStruct((2, self.edge_offsets[-1]), jnp.int32),
        jax.ShapeDtypeStruct((self.edge_offsets[-1],), jnp.bool_))
    mine = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    theirs = jax.tree.map(lambda a: (a.shape, str(a.dtype)), spec)
    if mine != theirs:
      raise RuntimeError(f'mesh_node: the program model builds {theirs}, '
                         f'the reference {mine}')
    tx = optax.adam(self.lr)
    state = train_lib.TrainState(params, tx.init(params),
                                 jnp.zeros((), jnp.int32))
    return state, tx, jax.device_get(params)

  def feature_counters(self):
    """The program's own feature-store counters so far (published once
    an epoch): what ``mesh_cache_hit_share`` and ``exchange_overflow``
    read, as deltas."""
    import graphlearn_tpu as glt
    names = ['dist_feature.' + k for k in ('hits', 'lookups', 'overflow',
                                           'unique_misses')]
    names += [f'dist_exchange.rows.hop{h}' for h in range(len(self.fanout))]
    return {k: int(glt.utils.counter_get(k)) for k in names}

  # ------------------------------------------------------ for ``run.py``

  def shapes(self):
    return dict(caps=self.caps, node_rows=self.node_offsets[-1],
                edge_slots=self.edge_offsets[-1], partitions=self.parts,
                cache_rows=self.dataset.node_features.cache_rows,
                rows_per_shard=self.dataset.node_features.n_max,
                resident_bytes=self.resident_bytes)

  def step_flops(self, nodes, edges):
    """ONE chip's share of a step: the operations its shard batch
    requires (``valid_counts`` gives the mean over shards)."""
    return flops_mesh_node.step_flops(self.model_desc, nodes, edges)

  def collate_bytes(self, nodes):
    return flops_mesh_node.collate_bytes(sum(nodes), self.feat_dim, 4)

  def exchange_bytes(self, counters, steps):
    """VALID bytes a chip's exchanges carried off it per step, from the
    program's counters over ``steps`` steps (summed over shards): each
    hop's ids out and neighbours back, the miss-only row exchange's ids
    out and rows back."""
    per = lambda k: counters.get(k, 0) / steps / self.parts
    out = {f'hop{h}': flops_mesh_node.hop_exchange_bytes(
        per(f'dist_exchange.rows.hop{h}'), k)
           for h, k in enumerate(self.fanout)}
    out['rows'] = flops_mesh_node.row_exchange_bytes(
        per('dist_feature.unique_misses'), self.parts, self.feat_dim)
    return out

  def valid_counts(self, batches):
    """Mean valid node rows and valid edges per hop over every SHARD
    batch of the replayed steps, and a shard's node-buffer rows: one
    chip's share, so ``step_mfu`` and ``pad_share`` read one chip."""
    eo = (0,) + tuple(self.edge_offsets)
    nodes, edges = [], []
    for b in batches:
      nsn, em = np.asarray(b['num_sampled_nodes']), np.asarray(b['edge_mask'])
      for p in range(nsn.shape[0]):
        nodes.append(nsn[p].reshape(-1).tolist())
        edges.append([int(em[p, eo[h]:eo[h + 1]].sum())
                      for h in range(len(eo) - 1)])
    return dict(nodes=np.mean(nodes, 0).tolist(),
                edges=np.mean(edges, 0).tolist(),
                buffer_rows=int(self.node_offsets[-1]))

  def _host_csr(self):
    """Per shard ``(indptr, indices)`` on the host, as the generator made
    them: fetched once, one shard at a time, never joined."""
    if self._csr is None:
      indptr, indices = self._csr_dev
      take = lambda a: [np.asarray(s.data[0]) for s in sorted(
          a.addressable_shards, key=lambda s: s.index[0].start or 0)]
      self._csr = (take(indptr), take(indices))
      self._csr_dev = None
    return self._csr

  def degrees(self, ids):
    """Global out-degree of nodes ``ids`` (node v is row v // P of shard
    v % P)."""
    indptr, _ = self._host_csr()
    deg = np.zeros(ids.shape[0], np.int64)
    for p in range(self.parts):
      m = ids % self.parts == p
      r = ids[m] // self.parts
      deg[m] = indptr[p][r + 1].astype(np.int64) - indptr[p][r]
    return deg

  def edge_keys(self, front):
    """Sorted ``node * N + neighbour`` keys of every out-edge of the
    nodes ``front``, from the shards that own them."""
    indptr, indices = self._host_csr()
    keys = []
    for p in range(self.parts):
      own = front[front % self.parts == p]
      r = own // self.parts
      lo = indptr[p][r].astype(np.int64)
      deg = indptr[p][r + 1].astype(np.int64) - lo
      seg = np.repeat(lo, deg) + (np.arange(int(deg.sum())) -
                                  np.repeat(np.cumsum(deg) - deg, deg))
      keys.append(np.repeat(own, deg) * self.num_nodes + indices[p][seg])
    return np.sort(np.concatenate(keys)) if keys else np.zeros(0, np.int64)

  def exact_numbers(self, batches, n):
    """The limit-0 numbers: the first ``n`` steps' P shard batches, each
    against the generator's arrays of the GLOBAL graph — every sampled
    pair is an edge, every expanded node has ``min(global out-degree, k)``
    sampled edges (a neighbour list the exchange cut short shows here),
    no node twice, every gathered row (cached, local or remote) and label
    the generator's for that id bit for bit, no seed in two shards of a
    step; over every replayed step the caps' ``overflow``; and
    ``exchange_overflow``, the miss-exchange buckets that spilled to
    their full-width fallback over the whole run so far."""
    out = dict.fromkeys(EXACT, 0)
    out['overflow'] = int(sum(bool(np.any(b['overflow'])) for b in batches))
    out['exchange_overflow'] = self.feature_counters()[
        'dist_feature.overflow']
    eo = (0,) + tuple(self.edge_offsets)
    for b in batches[:n]:
      seeds = []
      for p in range(self.parts):
        node = np.asarray(b['node'][p]).astype(np.int64)
        nsn = np.asarray(b['num_sampled_nodes'][p]).astype(np.int64)
        valid = int(nsn.sum())
        ids = node[:valid]
        seeds.append(ids[:int(nsn[0])])
        out['dup_nodes'] += int(valid - np.unique(ids).size) + int(
            (ids < 0).sum() + (ids >= self.num_nodes).sum())
        ei, em = np.asarray(b['edge_index'][p]), np.asarray(b['edge_mask'][p])
        brought = np.zeros(valid, bool)
        brought[:int(nsn[0])] = True
        expanded = 0
        for h, k in enumerate(self.fanout):
          m = em[eo[h]:eo[h + 1]]
          src = ei[0, eo[h]:eo[h + 1]][m].astype(np.int64)
          tgt = ei[1, eo[h]:eo[h + 1]][m].astype(np.int64)
          inside = (src >= 0) & (src < valid) & (tgt >= 0) & (tgt < valid)
          out['bad_edges'] += int((~inside).sum())
          src, tgt = src[inside], tgt[inside]
          brought[src] = True
          lo, hi = expanded, expanded + int(nsn[h])
          expanded = hi
          out['bad_edges'] += int(((tgt < lo) | (tgt >= hi)).sum())
          front = ids[lo:hi]
          got = np.bincount(tgt - lo, minlength=hi - lo)[:hi - lo]
          out['fanout_misses'] += int(
              (got != np.minimum(self.degrees(front), k)).sum())
          keys = self.edge_keys(front)
          want = ids[tgt] * self.num_nodes + ids[src]
          pos = np.minimum(np.searchsorted(keys, want),
                           max(keys.size - 1, 0))
          found = keys[pos] == want if keys.size else np.zeros(want.size,
                                                               bool)
          out['bad_edges'] += int((~found).sum())
        out['dup_nodes'] += int((~brought).sum())
        x = np.asarray(b['x'][p])[:valid]
        out['bad_rows'] += int((x.view(np.uint32) != self.rows(
            ids).view(np.uint32)).any(1).sum())
        y = np.asarray(b['y'][p])
        nlab = min(y.shape[0], valid)
        out['bad_rows'] += int((y[:nlab] != self.labels(ids[:nlab])).sum())
      seeds = np.concatenate(seeds)
      out['seed_overlap'] += int(seeds.size - np.unique(seeds).size)
    return out

  def rows(self, ids, xp=np):
    """The generator's rows for node ids, regenerated (host by default)."""
    return datagen.rows_of(xp, ids, self.graph_seed, self.num_classes,
                           xp.asarray(self.centres))

  def labels(self, ids, xp=np):
    return datagen.labels_of(xp, ids, self.graph_seed, self.num_classes)

  def follower(self, params0, batches):
    """``follow(lr=, compute_dtype=, half_batch=, precision=)`` of the
    plain reference (``perfbench/reference_mesh_node.py``) over the
    replayed steps from ``params0``: per step the P shard batches, rows
    regenerated by node id on the reference's one device."""
    import jax.numpy as jnp
    steps = [[self.reference_batch(np.asarray(b['node'][p]),
                                   np.asarray(b['edge_index'][p]),
                                   np.asarray(b['edge_mask'][p]))
              for p in range(self.parts)] for b in batches]
    rows = lambda ids: self.rows(ids, jnp)
    return lambda lr=self.lr, **kw: reference.follow(
        self.model_desc, lr, self.batch, params0, steps, rows, **kw)

  def reference_batch(self, node, edge_index, edge_mask):
    """A replayed shard batch as the reference wants it: node ids (the
    reference regenerates the rows), labels by the generator's own law,
    the local edge list with masked slots at 0."""
    safe = np.maximum(node, 0)
    return dict(
        ids=safe.astype(np.int32), live=node >= 0,
        y=self.labels(safe[:self.batch].astype(np.int64)).astype(np.int32),
        src=np.where(edge_mask, edge_index[0], 0).astype(np.int32),
        tgt=np.where(edge_mask, edge_index[1], 0).astype(np.int32),
        emask=np.asarray(edge_mask))
