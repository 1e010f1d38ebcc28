"""Family ``tiered_node``: supervised node classification on one homogeneous
graph whose FEATURE TABLE DOES NOT FIT THE CHIP — topology and the hottest
rows (by in-degree) resident in HBM, every other row in host memory —
through ``Dataset(graph=Graph(Topology.from_csr(...)), node_features=
TieredFeature.from_tiers(...))`` -> ``estimate_frontier_caps`` ->
``NeighborLoader(dedup=..., frontier_caps)`` -> a ``merge_dense`` model
under ``storage.TieredScanTrainer`` (``executors/tiered_scan.py``).

The dataset is the CONFIGURATION's: the graph by
``perfbench/datagen_tiered_node.py`` (``datagen_mesh_node.py``'s programs at
ONE partition, bit for bit — its law; the keys fold with the partition, so
the bytes are another draw than the four-partition graph's), a row as a pure
function of (``graph_seed``, node id). The hot prefix is generated on the
chip and stays there; the warm tier is generated on the chip a block at a
time and filled into ONE host array, which the store adopts. Nothing of size
N x F is ever in two places.

What the host keeps for ``correct``: the CSR as the generator made it (it
is also what the caps are calibrated on); rows and labels are regenerated
from node ids, never read back from the store under test.
"""
import threading
import time

import numpy as np

# what this family needs of the program, asked for before any data is
# made: a program without the no-copy door or the no-sort topology has
# neither name, and a run on it ends here, within seconds
from graphlearn_tpu.data import Graph, Topology, hot_first_order
from graphlearn_tpu.storage import TieredFeature

from perfbench import check, datagen_tiered_node as datagen
from perfbench import flops_tiered_node, reference_tiered_node as reference
from perfbench.datagen_mesh_node import labels_of, rows_of

if not hasattr(TieredFeature, 'from_tiers') or \
    not hasattr(Topology, 'from_csr'):
  raise SystemExit('tiered_node: this program has no '
                   'TieredFeature.from_tiers / Topology.from_csr')

EXACT = ('bad_edges', 'fanout_misses', 'dup_nodes', 'bad_rows', 'overflow',
         'bad_hot_rows', 'unplanned_rows')
WARM_BLOCK = 1 << 20        # rows generated and fetched at a time
HOT_SAMPLE = 4096           # hot rows checked against the generator


class _ByNodeId:
  """``table[ids]`` for ``check.validate_batches``: the generator's value
  for each node id, regenerated — never the store's."""

  def __init__(self, fn):
    self._fn = fn

  def __getitem__(self, ids):
    return self._fn(np.asarray(ids))


class Cell:
  """What a configuration builds once per process. ``run.py`` and
  ``control.py`` call ``shapes``, ``exact_numbers`` and ``follower``; the
  executor ``make_loader``, ``make_model``, ``make_state``,
  ``valid_counts``, ``tier_counters`` and reads ``batch``,
  ``num_classes``, ``steps_per_call``; the readers ``step_flops`` and
  ``gather_bytes``; ``check.validate_batches`` ``indptr``, ``indices``,
  ``edge_offsets``, ``fanout``, ``feat`` and ``label``."""

  def __init__(self, cfg, traffic, log):
    import jax

    import graphlearn_tpu as glt
    from graphlearn_tpu.models import train as train_lib
    self.cfg, self.traffic = cfg, traffic
    d, m, fs = cfg['dataset'], cfg['model'], cfg['feature_store']
    if m.get('matmul_precision'):
      jax.config.update('jax_default_matmul_precision',
                        m['matmul_precision'])
    n, f = int(d['num_nodes']), int(d['feat_dim'])
    self.num_nodes, self.num_classes = n, int(d['num_classes'])
    self.feat_dim, self.graph_seed = f, cfg['graph_seed']
    assert fs['hotness'] == 'in_degree' and not fs['disk_rows'], fs
    self.hot_rows = int(fs['hot_rows'])
    if self.hot_rows != round(fs["split_ratio"] * n):
      raise ValueError(f'tiered_node: hot_rows {self.hot_rows} is not '
                       f'split_ratio {fs["split_ratio"]} of {n} rows')

    # the warm tier's host array, its pages touched by a thread of their
    # own while the chip draws the graph: first-touch faults of 16 GB are
    # a third of the time it takes to fill them afterwards
    warm = np.empty((n - self.hot_rows, f), np.float32)
    touch = threading.Thread(target=_touch, args=(warm,), daemon=True)
    touch.start()

    # ---- the graph: drawn on the chip by the mesh generator's law at one
    # partition, fetched to the host once (what the caps are calibrated on
    # and the exact numbers counted against), in-degree counted there
    data = datagen.generate(
        n, d['num_directed_edges'], self.num_classes, f, d['p_intra'],
        d['feat_snr'], d['num_train'], cfg['graph_seed'],
        d['powerlaw_dmax'], log=log)
    self.indptr, self.indices = data['indptr'], data['indices']
    self.centres, self.train_idx = data['centres'], data['train_idx']

    # ---- the rows: hot prefix on the chip, the rest into one host array
    t0 = time.perf_counter()
    self.index2id, self.id2index = hot_first_order(data.pop('in_degree'),
                                                   self.hot_rows)
    log('order_s', time.perf_counter() - t0)
    t0 = time.perf_counter()
    touch.join()
    hot = self._make_rows(jax, warm)
    log('generate_rows_s', time.perf_counter() - t0)

    t0 = time.perf_counter()
    ds = glt.data.Dataset()
    # the topology as it is: no sort of E keys, no edge ids placed
    ds.graph = Graph(Topology.from_csr(self.indptr, self.indices, n), 'HBM')
    ds.graph.lazy_init()
    ds.node_features = TieredFeature.from_tiers(hot, warm,
                                                id2index=self.id2index)
    ds.init_node_labels(self.labels(np.arange(n, dtype=np.int32)))
    self.dataset = ds
    log('dataset_s', time.perf_counter() - t0)

    t0 = time.perf_counter()
    self.fanout = list(m['fanout'])
    self.batch = int(m['batch_size'])
    cal = traffic['calibration']
    self.caps = [int(c) for c in glt.sampler.estimate_frontier_caps(
        ds.graph, self.fanout, self.batch, input_nodes=self.train_idx,
        num_probes=cal['num_probes'], slack=cal['slack'],
        seed=cal['seed'])]
    log('calibrate_s', time.perf_counter() - t0)
    self.node_offsets, self.edge_offsets = train_lib.merge_hop_offsets(
        self.batch, self.fanout, None, self.caps)
    self.model_desc = dict(
        kind=m['kind'], in_dim=f, hidden=m['hidden'],
        out_dim=self.num_classes, layers=len(self.fanout), heads=1)
    if m['kind'] != 'sage':
      raise ValueError(f'tiered_node: unknown model kind {m["kind"]!r}')
    self.lr = float(m['lr'])
    self.steps_per_call = int(cfg['steps_per_call'])
    # what ``check.validate_batches`` gathers rows and labels from
    self.feat = _ByNodeId(self.rows)
    self.label = _ByNodeId(self.labels)
    self.resident_bytes = (jax.devices()[0].memory_stats() or {}).get(
        'bytes_in_use', 0)

  def _make_rows(self, jax, warm):
    """The hot prefix ``[H, F]`` as a device array, and ``warm`` (the host's
    ``[N - H, F]``) filled, both in storage order: row r is node
    ``index2id[r]``'s. The hot prefix is one program that fills it a piece
    at a time; the warm tier is generated a block at a time and copied out
    while the next block is made."""
    import jax.numpy as jnp
    from jax import lax
    h, f = self.hot_rows, self.feat_dim
    seed, c = self.graph_seed, self.num_classes
    # every array placed WITHOUT naming a device: an array committed to
    # one makes the chunk's outputs committed too, and the trainer's
    # first chunk, its later chunks and a later call's first chunk are
    # then three signatures of one program (three compiles of a minute)
    centre = jax.device_put(self.centres)
    piece = min(1 << 18, h)

    @jax.jit
    def hot_rows(ids, centre):
      def fill(i, feats):
        # the last piece starts early enough to end at H: a row is a
        # function of its id, so rows written twice are written the same
        at0 = jnp.minimum(i * piece, h - piece)
        at = lax.dynamic_slice(ids, (at0,), (piece,))
        return lax.dynamic_update_slice(
            feats, rows_of(jnp, at, seed, c, centre), (at0, 0))
      return lax.fori_loop(0, -(-h // piece), fill,
                           jnp.zeros((h, f), jnp.float32))

    block_rows = jax.jit(
        lambda ids, centre: rows_of(jnp, ids, seed, c, centre))
    hot = hot_rows(jax.device_put(self.index2id[:h]), centre)
    w = warm.shape[0]
    blk = min(WARM_BLOCK, max(w, 1))
    pending = None
    for a in range(0, w, blk):
      ids = np.zeros((blk,), np.int32)
      n = min(blk, w - a)
      ids[:n] = self.index2id[h + a:h + a + n]
      x = block_rows(jax.device_put(ids), centre)
      x.copy_to_host_async()
      if pending is not None:
        warm[pending[0]:pending[0] + pending[1]] = \
            np.asarray(pending[2])[:pending[1]]
      pending = (a, n, x)
    if pending is not None:
      warm[pending[0]:pending[0] + pending[1]] = \
          np.asarray(pending[2])[:pending[1]]
    jax.block_until_ready(hot)
    return hot

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
    traffic = self.traffic
    return glt.loader.NeighborLoader(
        self.dataset, self.fanout, self.train_idx, batch_size=self.batch,
        shuffle=bool(traffic['shuffle']), drop_last=True,
        seed=int(seed) % (2 ** 31 - 1), dedup=traffic['dedup'],
        frontier_caps=self.caps,
        seed_labels_only=bool(self.cfg['feature_store']['seed_labels_only']))

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
      raise RuntimeError(f'tiered_node: the program model builds {theirs}, '
                         f'the reference {mine}')
    tx = optax.adam(self.lr)
    state = train_lib.TrainState(params, tx.init(params),
                                 jnp.zeros((), jnp.int32))
    return state, tx, jax.device_get(params)

  def tier_counters(self):
    """The program's own ``storage.*`` counters so far (published once a
    call): what the ``tier_*`` share readers take as deltas over the
    window."""
    import graphlearn_tpu as glt
    return {k: int(glt.utils.counter_get('storage.' + k)) for k in (
        'lookups', 'hot_hits', 'planned_rows', 'slab_cap_rows',
        'staged_bytes', 'prefetch_miss')}

  # ------------------------------------------------------ for ``run.py``

  def shapes(self):
    store = self.dataset.node_features
    return dict(caps=self.caps, node_rows=self.node_offsets[-1],
                edge_slots=self.edge_offsets[-1], hot_rows=store.hot_rows,
                warm_rows=store.warm_rows,
                table_bytes=int(self.num_nodes) * self.feat_dim * 4,
                resident_bytes=self.resident_bytes,
                host_peak_rss_bytes=_peak_rss())

  def step_flops(self, nodes, edges):
    return flops_tiered_node.step_flops(self.model_desc, nodes, edges)

  def gather_bytes(self, nodes):
    return flops_tiered_node.gather_bytes(sum(nodes), self.feat_dim, 4)

  def valid_counts(self, batches):
    """Mean valid node rows per hop and valid edges per hop over host
    batches, and the node buffer's rows (``homo_node``'s counts)."""
    eo = (0,) + tuple(self.edge_offsets)
    nodes = [np.asarray(b['num_sampled_nodes']).reshape(-1).tolist()
             for b in batches]
    edges = [[int(np.asarray(b['edge_mask'])[eo[h]:eo[h + 1]].sum())
              for h in range(len(eo) - 1)] for b in batches]
    return dict(nodes=np.mean(nodes, 0).tolist(),
                edges=np.mean(edges, 0).tolist(),
                buffer_rows=int(self.node_offsets[-1]))

  def rows(self, ids, xp=np):
    """The generator's rows for node ids, regenerated (host by default)."""
    return rows_of(xp, ids, self.graph_seed, self.num_classes,
                   xp.asarray(self.centres))

  def labels(self, ids, xp=np):
    return labels_of(xp, ids, self.graph_seed, self.num_classes)

  def exact_numbers(self, batches, n):
    """The limit-0 numbers. ``check.validate_batches``' five over the
    first ``n`` replayed batches, against the generator's CSR and its
    regenerated rows and labels — a batch's ``x`` came through the
    program's own ``tiered_gather`` with the slab the trainer staged for
    the first chunk, so a row read as zeros, a stale slab or a row of
    another id is a ``bad_rows``; ``bad_hot_rows``: of ``HOT_SAMPLE``
    rows of the device's hot prefix (a stride over it), those that are
    not the generator's rows for the ids the order puts there;
    ``unplanned_rows``: over EVERY replayed batch of the first chunk,
    valid node slots whose storage row is neither hot nor in the chunk's
    slab (``batches[0]['slab_ids']``, the trainer's own)."""
    out = check.validate_batches(self, batches, n)
    store = self.dataset.node_features
    h = store.hot_rows
    at = np.unique(np.linspace(0, h - 1, min(HOT_SAMPLE, h)).astype(np.int64))
    got = store._hot_host(at)
    want = self.rows(self.index2id[at])
    out['bad_hot_rows'] = int(
        (got.view(np.uint32) != want.view(np.uint32)).any(1).sum())
    slab_ids = np.asarray(batches[0]['slab_ids'])
    missing = 0
    for b in batches:
      valid = int(np.asarray(b['num_sampled_nodes']).sum())
      rows = self.id2index[np.asarray(b['node'])[:valid]]
      missing += int((~np.isin(rows[rows >= h], slab_ids)).sum())
    out['unplanned_rows'] = missing
    return out

  def follower(self, params0, batches):
    """``follow(lr=, compute_dtype=, half_batch=, precision=)`` of the
    plain reference (``perfbench/reference_tiered_node.py``) over the
    replayed batches from ``params0``, rows regenerated by node id on the
    reference's device."""
    import jax.numpy as jnp
    ref_in = [self.reference_batch(b['node'], b['edge_index'],
                                   b['edge_mask']) for b in batches]
    rows = lambda ids: self.rows(ids, jnp)
    return lambda lr=self.lr, **kw: reference.follow(
        self.model_desc, lr, self.batch, params0, ref_in, rows, **kw)

  def reference_batch(self, node, edge_index, edge_mask):
    """A replayed batch as the reference wants it: node ids (the
    reference regenerates the rows), labels by the generator's own law,
    the local edge list with masked slots at 0."""
    node = np.asarray(node)
    safe = np.maximum(node, 0)
    em, ei = np.asarray(edge_mask), np.asarray(edge_index)
    return dict(
        ids=safe.astype(np.int32), live=node >= 0,
        y=self.labels(safe[:self.batch]).astype(np.int32),
        src=np.where(em, ei[0], 0).astype(np.int32),
        tgt=np.where(em, ei[1], 0).astype(np.int32), emask=em)


def _touch(array, rows=1 << 18):
  """Write every page of a fresh host array once (numpy releases the
  interpreter lock for the fill)."""
  for a in range(0, array.shape[0], rows):
    array[a:a + rows] = 0


def _peak_rss():
  """The process's peak resident set, bytes (``ru_maxrss`` is KiB on
  Linux)."""
  import resource
  return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
