"""Model tests: conv correctness on tiny graphs + end-to-end training on a
synthetic task (the framework's MVP gate, SURVEY.md §7.4)."""
import numpy as np
import pytest

import graphlearn_tpu as glt


def small_batch(n=6, f=4, e=8):
  import jax.numpy as jnp
  rng = np.random.default_rng(0)
  x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
  row = jnp.asarray([0, 1, 2, 3, 4, 5, -1, -1], jnp.int32)
  col = jnp.asarray([1, 2, 3, 4, 5, 0, -1, -1], jnp.int32)
  ei = jnp.stack([row, col])
  em = jnp.asarray([True] * 6 + [False] * 2)
  return x, ei, em


def test_sage_conv_mean_agg():
  import jax
  import jax.numpy as jnp
  x, ei, em = small_batch()
  conv = glt.models.SAGEConv(8)
  params = conv.init(jax.random.PRNGKey(0), x, ei, em)
  out = conv.apply(params, x, ei, em)
  assert out.shape == (6, 8)
  # padding edges must not contribute: flipping padded entries is a no-op
  ei2 = ei.at[:, 6:].set(0)
  out2 = conv.apply(params, x, ei2, jnp.asarray(em))
  np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-5)


@pytest.mark.parametrize('cls', ['gcn', 'gat'])
def test_conv_shapes(cls):
  import jax
  x, ei, em = small_batch()
  conv = (glt.models.GCNConv(8) if cls == 'gcn'
          else glt.models.GATConv(4, heads=2))
  params = conv.init(jax.random.PRNGKey(0), x, ei, em)
  out = conv.apply(params, x, ei, em)
  assert out.shape == (6, 8)
  assert np.isfinite(np.asarray(out)).all()


def make_cluster_dataset(n_per=40, f=8):
  """Two clusters with distinct features + dense intra-cluster edges; labels
  = cluster. GraphSAGE should fit it quickly."""
  rng = np.random.default_rng(1)
  n = 2 * n_per
  x = np.zeros((n, f), np.float32)
  x[:n_per, : f // 2] = 1.0 + 0.1 * rng.normal(size=(n_per, f // 2))
  x[n_per:, f // 2:] = 1.0 + 0.1 * rng.normal(size=(n_per, f // 2))
  rows, cols = [], []
  for c in range(2):
    base = c * n_per
    for i in range(n_per):
      for j in rng.choice(n_per, 4, replace=False):
        rows.append(base + i)
        cols.append(base + int(j))
  y = np.repeat([0, 1], n_per)
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([np.array(rows), np.array(cols)]),
                graph_mode='CPU', num_nodes=n)
  ds.init_node_features(x)
  ds.init_node_labels(y)
  return ds


def test_train_graphsage_end_to_end():
  import jax
  ds = make_cluster_dataset()
  loader = glt.loader.NeighborLoader(ds, [4, 4], np.arange(80),
                                     batch_size=16, shuffle=True, seed=0)
  model = glt.models.GraphSAGE(hidden_dim=16, out_dim=2, num_layers=2)
  first = glt.models.batch_to_dict(next(iter(loader)))
  state, tx = glt.models.create_train_state(model, jax.random.PRNGKey(0),
                                            first, lr=1e-2)
  train_step, eval_step = glt.models.make_train_step(model, tx,
                                                     num_classes=2)
  accs = []
  for _ in range(4):
    for batch in loader:
      state, loss, acc = train_step(state, glt.models.batch_to_dict(batch))
    accs.append(float(acc))
  assert accs[-1] > 0.9, accs


def test_layered_forward_matches_full():
  """The layered (hop-sliced) GraphSAGE forward over tree-mode batches is
  numerically identical to the full forward on the seed slots — it only
  drops rows that cannot influence them."""
  import jax
  from graphlearn_tpu.models import train as train_lib
  rng = np.random.default_rng(0)
  n = 200
  rows = rng.integers(0, n, 2000)
  cols = rng.integers(0, n, 2000)
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 16)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 4, n))
  loader = glt.loader.NeighborLoader(ds, [3, 2], np.arange(32),
                                     batch_size=16, seed=0, dedup='tree')
  b = train_lib.batch_to_dict(next(iter(loader)))
  no, eo = train_lib.tree_hop_offsets(16, [3, 2])
  full = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2)
  layered = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2,
                                 hop_node_offsets=no, hop_edge_offsets=eo)
  params = full.init(jax.random.PRNGKey(0), b['x'], b['edge_index'],
                     b['edge_mask'])
  out_full = np.asarray(full.apply(params, b['x'], b['edge_index'],
                                   b['edge_mask']))
  out_lay = np.asarray(layered.apply(params, b['x'], b['edge_index'],
                                     b['edge_mask']))
  nseed = int(b['num_seed_nodes'])
  np.testing.assert_allclose(out_full[:nseed], out_lay[:nseed], rtol=1e-5)
  # a layered train step runs and converges direction-wise
  state, tx = train_lib.create_train_state(layered, jax.random.PRNGKey(0),
                                           b)
  step, _ = train_lib.make_train_step(layered, tx, 4)
  state, loss, acc = step(state, b)
  assert np.isfinite(float(loss))


def test_layered_forward_matches_full_merge_batches():
  """Layered prefix-trimming on exact-dedup (merge) batches: seed
  logits identical to the full forward, including under calibrated
  frontier caps."""
  import jax
  from graphlearn_tpu.models import train as train_lib
  rng = np.random.default_rng(7)
  n = 300
  rows = rng.integers(0, n, 3000)
  cols = rng.integers(0, n, 3000)
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 16)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 4, n))
  for caps in (None, [40, 72]):
    loader = glt.loader.NeighborLoader(ds, [3, 2], np.arange(48),
                                       batch_size=16, seed=0, dedup='map',
                                       frontier_caps=caps,
                                       overflow_policy='off')
    no, eo = train_lib.merge_hop_offsets(16, [3, 2], frontier_caps=caps)
    full = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2)
    layered = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2,
                                   hop_node_offsets=no,
                                   hop_edge_offsets=eo)
    for i, batch in enumerate(loader):
      b = train_lib.batch_to_dict(batch)
      if i == 0:
        params = full.init(jax.random.PRNGKey(0), b['x'],
                           b['edge_index'], b['edge_mask'])
      out_full = np.asarray(full.apply(params, b['x'], b['edge_index'],
                                       b['edge_mask']))
      out_lay = np.asarray(layered.apply(params, b['x'], b['edge_index'],
                                         b['edge_mask']))
      nseed = int(b['num_seed_nodes'])
      np.testing.assert_allclose(out_full[:nseed], out_lay[:nseed],
                                 rtol=1e-5, atol=1e-5)


def test_merge_dense_matches_segment():
  """MergeSAGEConv's blocked aggregation == the segment-op SAGEConv on
  merge batches (seed logits identical), including calibrated caps."""
  import jax
  from graphlearn_tpu.models import train as train_lib
  rng = np.random.default_rng(13)
  n = 400
  rows = rng.integers(0, n, 4000)
  cols = rng.integers(0, n, 4000)
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 16)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 4, n))
  for caps in (None, [48, 104]):
    loader = glt.loader.NeighborLoader(ds, [4, 3], np.arange(64),
                                       batch_size=16, seed=0, dedup='map',
                                       frontier_caps=caps,
                                       overflow_policy='off')
    no, eo = train_lib.merge_hop_offsets(16, [4, 3], frontier_caps=caps)
    seg = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2,
                               hop_node_offsets=no, hop_edge_offsets=eo)
    dense = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2,
                                 hop_node_offsets=no, hop_edge_offsets=eo,
                                 merge_dense=True, fanouts=(4, 3))
    params = None
    for batch in loader:
      b = train_lib.batch_to_dict(batch)
      if params is None:
        params = seg.init(jax.random.PRNGKey(0), b['x'],
                          b['edge_index'], b['edge_mask'])
      out_seg = np.asarray(seg.apply(params, b['x'], b['edge_index'],
                                     b['edge_mask']))
      out_dense = np.asarray(dense.apply(params, b['x'], b['edge_index'],
                                         b['edge_mask']))
      nseed = int(b['num_seed_nodes'])
      np.testing.assert_allclose(out_seg[:nseed], out_dense[:nseed],
                                 rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # tier-1 budget: SAGE merge-dense variant stays tier-1
def test_merge_dense_gat_matches_segment():
  """MergeGATConv's per-target k-run softmax == segment-softmax GATConv
  on merge batches (seed logits identical), incl. calibrated caps."""
  import jax
  from graphlearn_tpu.models import train as train_lib
  rng = np.random.default_rng(17)
  n = 300
  rows = rng.integers(0, n, 3000)
  cols = rng.integers(0, n, 3000)
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 12)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 4, n))
  for caps in (None, [40, 88]):
    loader = glt.loader.NeighborLoader(ds, [4, 3], np.arange(48),
                                       batch_size=16, seed=0, dedup='map',
                                       frontier_caps=caps,
                                       overflow_policy='off')
    no, eo = train_lib.merge_hop_offsets(16, [4, 3], frontier_caps=caps)
    seg = glt.models.GAT(hidden_dim=12, out_dim=4, num_layers=2, heads=2,
                         hop_node_offsets=no, hop_edge_offsets=eo)
    dense = glt.models.GAT(hidden_dim=12, out_dim=4, num_layers=2,
                           heads=2, hop_node_offsets=no,
                           hop_edge_offsets=eo, merge_dense=True,
                           fanouts=(4, 3))
    params = None
    for batch in loader:
      b = train_lib.batch_to_dict(batch)
      if params is None:
        params = seg.init(jax.random.PRNGKey(0), b['x'],
                          b['edge_index'], b['edge_mask'])
      out_seg = np.asarray(seg.apply(params, b['x'], b['edge_index'],
                                     b['edge_mask']))
      out_dense = np.asarray(dense.apply(params, b['x'], b['edge_index'],
                                         b['edge_mask']))
      nseed = int(b['num_seed_nodes'])
      np.testing.assert_allclose(out_seg[:nseed], out_dense[:nseed],
                                 rtol=2e-4, atol=2e-4)


def test_hgt_param_structure_batch_independent():
  """HGTConv materializes per-node-type params for EVERY metadata type,
  so a type absent at init but present at a later apply (or vice versa)
  neither fails nor changes the param tree."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.models.hgt import HGTConv
  ntypes = ['a', 'b']
  etypes = [('a', 'r', 'b')]
  conv = HGTConv(out_dim=8, metadata=(ntypes, etypes), heads=2)
  ei = jnp.zeros((2, 4), jnp.int32)
  em = jnp.ones((4,), bool)
  # init WITHOUT type 'a' present
  params = conv.init(jax.random.PRNGKey(0),
                     {'b': jnp.ones((3, 8))},
                     {}, {})
  # apply WITH both types — params for 'a' must already exist
  out = conv.apply(params, {'a': jnp.ones((2, 8)),
                            'b': jnp.ones((3, 8))},
                   {('a', 'r', 'b'): ei}, {('a', 'r', 'b'): em})
  assert set(out) == {'a', 'b'}
  # param tree identical when initialized with the full dict
  params2 = conv.init(jax.random.PRNGKey(0),
                      {'a': jnp.ones((2, 8)), 'b': jnp.ones((3, 8))},
                      {('a', 'r', 'b'): ei}, {('a', 'r', 'b'): em})
  t1 = jax.tree_util.tree_structure(params)
  t2 = jax.tree_util.tree_structure(params2)
  assert t1 == t2


def test_bf16_model_path():
  """dtype=bfloat16 models: params stay f32, outputs are bf16, training
  converges on the cluster task, and bf16 outputs track f32 closely."""
  import jax
  import jax.numpy as jnp
  ds = make_cluster_dataset()
  loader = glt.loader.NeighborLoader(ds, [4, 4], np.arange(80),
                                     batch_size=16, shuffle=True, seed=0)
  model = glt.models.GraphSAGE(hidden_dim=16, out_dim=2, num_layers=2,
                               dtype=jnp.bfloat16)
  first = glt.models.batch_to_dict(next(iter(loader)))
  state, tx = glt.models.create_train_state(model, jax.random.PRNGKey(0),
                                            first, lr=1e-2)
  # params are stored in f32 (master weights), compute casts to bf16
  leaf = jax.tree_util.tree_leaves(state.params)[0]
  assert leaf.dtype == jnp.float32
  out = model.apply(state.params, first['x'], first['edge_index'],
                    first['edge_mask'])
  assert out.dtype == jnp.bfloat16
  # f32 twin with the SAME params agrees to bf16 tolerance
  f32 = glt.models.GraphSAGE(hidden_dim=16, out_dim=2, num_layers=2)
  ref = f32.apply(state.params, first['x'], first['edge_index'],
                  first['edge_mask'])
  np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                             atol=0.15, rtol=0.1)
  train_step, _ = glt.models.make_train_step(model, tx, num_classes=2)
  for _ in range(4):
    for batch in loader:
      state, loss, acc = train_step(state, glt.models.batch_to_dict(batch))
  assert float(acc) > 0.9


def test_bf16_conv_variants():
  import jax
  import jax.numpy as jnp
  x, ei, em = small_batch()
  for conv in (glt.models.GCNConv(8, dtype=jnp.bfloat16),
               glt.models.GATConv(4, heads=2, dtype=jnp.bfloat16),
               glt.models.SAGEConv(8, dtype=jnp.bfloat16)):
    params = conv.init(jax.random.PRNGKey(0), x, ei, em)
    out = conv.apply(params, x, ei, em)
    assert out.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(out, np.float32)).all()


def make_hetero_cluster():
  """paper/author hetero graph with 2 paper communities; authorship is
  community-aligned so typed aggregation is informative."""
  rng = np.random.default_rng(3)
  n_p, n_a = 80, 40
  comm = (np.arange(n_p) % 2)
  # cites: intra-community
  pr = rng.integers(0, n_p, 600)
  pc = (pr + 2 * rng.integers(0, n_p // 2, 600)) % n_p
  # writes: author a writes papers of community a%2
  ar = np.repeat(np.arange(n_a), 4)
  ap = (ar % 2 + 2 * rng.integers(0, n_p // 2, ar.size)) % n_p
  feats = {'paper': rng.standard_normal((n_p, 8)).astype(np.float32),
           'author': (np.arange(n_a) % 2)[:, None].astype(np.float32) *
           np.ones((n_a, 8), np.float32)}
  ds = glt.data.Dataset()
  CITES = ('paper', 'cites', 'paper')
  WRITES = ('author', 'writes', 'paper')
  ds.init_graph({CITES: np.stack([pr, pc]), WRITES: np.stack([ar, ap])},
                graph_mode='CPU',
                num_nodes={CITES: n_p, WRITES: n_a})
  ds.init_node_features(feats)
  ds.init_node_labels({'paper': comm.astype(np.int64)})
  return ds, (CITES, WRITES), n_p


@pytest.mark.slow  # tier-1 budget (PR 18): HGT training e2e — the HGT
# equivalence tests (merge-dense, hierarchical) stay tier-1
def test_hgt_end_to_end():
  import jax
  import jax.numpy as jnp
  import optax
  ds, (CITES, WRITES), n_p = make_hetero_cluster()
  fanouts = {CITES: [4, 4], WRITES: [4, 4]}
  loader = glt.loader.NeighborLoader(
      ds, fanouts, ('paper', np.arange(n_p)), batch_size=16, shuffle=True,
      seed=0)
  etypes = [glt.typing.reverse_edge_type(CITES),
            glt.typing.reverse_edge_type(WRITES)]
  model = glt.models.HGT(ntypes=('paper', 'author'), etypes=tuple(etypes),
                         hidden_dim=16, out_dim=2, heads=4, num_layers=2,
                         out_ntype='paper')
  b = next(iter(loader))
  params = model.init(jax.random.PRNGKey(0), b.x, b.edge_index, b.edge_mask)
  out = model.apply(params, b.x, b.edge_index, b.edge_mask)
  assert out.shape == (b.x['paper'].shape[0], 2)
  assert np.isfinite(np.asarray(out)).all()
  # padding invariance: rewriting padded edge slots must not change output
  ei2 = {et: ei.at[:, -1].set(0) if bool((ei[0][-1] < 0)) else ei
         for et, ei in b.edge_index.items()}
  out2 = model.apply(params, b.x, ei2, b.edge_mask)
  np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-5)

  tx = optax.adam(1e-2)
  opt_state = tx.init(params)

  def loss_fn(params, b):
    logits = model.apply(params, b['x'], b['ei'], b['em'])
    seed_mask = jnp.arange(logits.shape[0]) < b['num_seed']
    ce = optax.softmax_cross_entropy(logits, jax.nn.one_hot(b['y'], 2))
    loss = jnp.where(seed_mask, ce, 0.0).sum() / jnp.maximum(
        seed_mask.sum(), 1)
    acc = (((logits.argmax(-1) == b['y']) & seed_mask).sum() /
           jnp.maximum(seed_mask.sum(), 1))
    return loss, acc

  @jax.jit
  def step(params, opt_state, b):
    (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
    updates, opt_state = tx.update(g, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss, acc

  def bdict(batch):
    return dict(x=batch.x, ei=batch.edge_index, em=batch.edge_mask,
                y=batch.y['paper'],
                num_seed=batch.num_sampled_nodes['paper'][0])

  for _ in range(6):
    for batch in loader:
      params, opt_state, loss, acc = step(params, opt_state, bdict(batch))
  assert float(acc) > 0.9, float(acc)


def test_hgt_bf16():
  import jax
  import jax.numpy as jnp
  ds, (CITES, WRITES), n_p = make_hetero_cluster()
  fanouts = {CITES: [4], WRITES: [4]}
  loader = glt.loader.NeighborLoader(
      ds, fanouts, ('paper', np.arange(n_p)), batch_size=16, seed=0)
  etypes = [glt.typing.reverse_edge_type(CITES),
            glt.typing.reverse_edge_type(WRITES)]
  model = glt.models.HGT(ntypes=('paper', 'author'), etypes=tuple(etypes),
                         hidden_dim=16, out_dim=2, num_layers=1,
                         out_ntype='paper', dtype=jnp.bfloat16)
  b = next(iter(loader))
  params = model.init(jax.random.PRNGKey(0), b.x, b.edge_index, b.edge_mask)
  assert jax.tree_util.tree_leaves(params)[0].dtype == jnp.float32
  out = model.apply(params, b.x, b.edge_index, b.edge_mask)
  assert out.dtype == jnp.bfloat16
  assert np.isfinite(np.asarray(out, np.float32)).all()


@pytest.mark.parametrize('dedup', [
    'tree', pytest.param('map', marks=pytest.mark.slow)])  # tier-1 budget
def test_hierarchical_rgnn_matches_full(dedup):
  """The hierarchical (trim-per-layer) RGNN forward matches the full
  forward on the seed slots — over hetero TREE batches and hetero
  exact-dedup (merge) batches alike: merge appends stay inside the same
  per-type hop-prefix bounds, so the identical offsets trim both."""
  import jax
  ds, (CITES, WRITES), n_p = make_hetero_cluster()
  fanouts = {CITES: [3, 2], WRITES: [2, 2]}
  loader = glt.loader.NeighborLoader(
      ds, fanouts, ('paper', np.arange(32)), batch_size=16, seed=0,
      dedup=dedup)
  b = next(iter(loader))
  etypes = [glt.typing.reverse_edge_type(CITES),
            glt.typing.reverse_edge_type(WRITES)]
  no, eo = glt.sampler.hetero_tree_layout({'paper': 16}, (CITES, WRITES),
                                          fanouts)
  # layout must match the engine's actual buffers
  for t, x in b.x.items():
    assert no[t][-1] == x.shape[0], (t, no[t], x.shape)
  for et, ei in b.edge_index.items():
    assert eo[tuple(et)][-1] == ei.shape[1], (et, eo[tuple(et)], ei.shape)
  full = glt.models.RGNN(etypes=tuple(etypes), hidden_dim=16, out_dim=4,
                         num_layers=2, out_ntype='paper')
  hier = glt.models.RGNN(etypes=tuple(etypes), hidden_dim=16, out_dim=4,
                         num_layers=2, out_ntype='paper',
                         hop_node_offsets=no, hop_edge_offsets=eo)
  params = full.init(jax.random.PRNGKey(0), b.x, b.edge_index, b.edge_mask)
  out_full = np.asarray(full.apply(params, b.x, b.edge_index, b.edge_mask))
  out_hier = np.asarray(hier.apply(params, b.x, b.edge_index, b.edge_mask))
  nseed = int(b.num_sampled_nodes['paper'][0])
  np.testing.assert_allclose(out_full[:nseed], out_hier[:nseed], rtol=1e-5)


def test_tree_dense_matches_segment():
  """GraphSAGE(tree_dense=True) — dense reshape aggregation over tree
  blocks — is numerically identical to the segment-op layered forward
  (same params, same batches), and trains."""
  import jax
  from graphlearn_tpu.models import train as train_lib
  rng = np.random.default_rng(0)
  n = 300
  rows = rng.integers(0, n, 3000)
  cols = rng.integers(0, n, 3000)
  keep = rows != n - 1                 # isolated node: zero-child parents
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows[keep], cols[keep]]), num_nodes=n,
                graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 12)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 4, n))
  loader = glt.loader.NeighborLoader(
      ds, [4, 3], np.array([n - 1] + list(range(15))), batch_size=16,
      seed=0, dedup='tree')
  b = train_lib.batch_to_dict(next(iter(loader)))
  no, eo = train_lib.tree_hop_offsets(16, [4, 3])
  seg = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2,
                             hop_node_offsets=no, hop_edge_offsets=eo)
  dense = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2,
                               hop_node_offsets=no, hop_edge_offsets=eo,
                               tree_dense=True, fanouts=(4, 3))
  params = seg.init(jax.random.PRNGKey(0), b['x'], b['edge_index'],
                    b['edge_mask'])
  o_seg = np.asarray(seg.apply(params, b['x'], b['edge_index'],
                               b['edge_mask']))
  # params are interchangeable by construction (same names)
  o_dense = np.asarray(dense.apply(params, b['x'], b['edge_index'],
                                   b['edge_mask']))
  np.testing.assert_allclose(o_seg, o_dense, rtol=2e-5, atol=2e-5)
  # trains end to end
  state, tx = train_lib.create_train_state(dense, jax.random.PRNGKey(0), b)
  step, _ = train_lib.make_train_step(dense, tx, 4)
  state, loss, acc = step(state, b)
  assert np.isfinite(float(loss))
  # node_budget (truncated blocks) must be rejected loudly
  no_b, eo_b = train_lib.tree_hop_offsets(16, [4, 3], node_budget=32)
  bad = glt.models.GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2,
                             hop_node_offsets=no_b, hop_edge_offsets=eo_b,
                             tree_dense=True, fanouts=(4, 3))
  loader_b = glt.loader.NeighborLoader(
      ds, [4, 3], np.arange(16), batch_size=16, seed=0, dedup='tree',
      node_budget=32)
  bb = train_lib.batch_to_dict(next(iter(loader_b)))
  import pytest
  with pytest.raises(AssertionError, match='un-truncated'):
    bad.init(jax.random.PRNGKey(0), bb['x'], bb['edge_index'],
             bb['edge_mask'])


def test_tree_dense_gat_matches_segment():
  """TreeGATConv (per-parent dense softmax) equals the segment-softmax
  GATConv on tree batches, for the full layered GAT stack."""
  import jax
  from graphlearn_tpu.models import train as train_lib
  rng = np.random.default_rng(1)
  n = 200
  rows = rng.integers(0, n, 2000)
  cols = rng.integers(0, n, 2000)
  keep = rows != n - 1               # zero-child parents exist
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows[keep], cols[keep]]), num_nodes=n,
                graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 12)).astype(np.float32))
  loader = glt.loader.NeighborLoader(
      ds, [4, 3], np.array([n - 1] + list(range(15))), batch_size=16,
      seed=0, dedup='tree')
  b = next(iter(loader))
  no, eo = train_lib.tree_hop_offsets(16, [4, 3])
  seg = glt.models.GAT(hidden_dim=16, out_dim=4, num_layers=2, heads=2,
                       hop_node_offsets=no, hop_edge_offsets=eo)
  dense = glt.models.GAT(hidden_dim=16, out_dim=4, num_layers=2, heads=2,
                         hop_node_offsets=no, hop_edge_offsets=eo,
                         tree_dense=True, fanouts=(4, 3))
  params = seg.init(jax.random.PRNGKey(0), b.x, b.edge_index, b.edge_mask)
  o_seg = np.asarray(seg.apply(params, b.x, b.edge_index, b.edge_mask))
  o_dense = np.asarray(dense.apply(params, b.x, b.edge_index,
                                   b.edge_mask))
  nseed = int(b.num_sampled_nodes[0])
  np.testing.assert_allclose(o_seg[:nseed], o_dense[:nseed],
                             rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize('dedup', [
    'tree', pytest.param('map', marks=pytest.mark.slow)])  # tier-1 budget
def test_hierarchical_hgt_matches_full(dedup):
  """HGT with hetero hop offsets (trim-per-layer) matches the full
  forward on the seed slots — tree and exact-dedup (merge) hetero
  batches alike (same per-type prefix bounds)."""
  import jax
  ds, (CITES, WRITES), n_p = make_hetero_cluster()
  fanouts = {CITES: [3, 2], WRITES: [2, 2]}
  loader = glt.loader.NeighborLoader(
      ds, fanouts, ('paper', np.arange(32)), batch_size=16, seed=0,
      dedup=dedup)
  b = next(iter(loader))
  etypes = tuple(glt.typing.reverse_edge_type(et)
                 for et in (CITES, WRITES))
  no, eo = glt.sampler.hetero_tree_layout({'paper': 16}, (CITES, WRITES),
                                          fanouts)
  full = glt.models.HGT(ntypes=('paper', 'author'), etypes=etypes,
                        hidden_dim=16, out_dim=4, heads=2, num_layers=2,
                        out_ntype='paper')
  hier = glt.models.HGT(ntypes=('paper', 'author'), etypes=etypes,
                        hidden_dim=16, out_dim=4, heads=2, num_layers=2,
                        out_ntype='paper', hop_node_offsets=no,
                        hop_edge_offsets=eo)
  params = full.init(jax.random.PRNGKey(0), b.x, b.edge_index, b.edge_mask)
  o_full = np.asarray(full.apply(params, b.x, b.edge_index, b.edge_mask))
  o_hier = np.asarray(hier.apply(params, b.x, b.edge_index, b.edge_mask))
  nseed = int(b.num_sampled_nodes['paper'][0])
  np.testing.assert_allclose(o_full[:nseed], o_hier[:nseed],
                             rtol=5e-5, atol=5e-5)


@pytest.mark.slow  # tier-1 budget (PR 16): zero-degree variant of
# test_merge_dense_matches_segment, which stays tier-1
def test_merge_dense_zero_degree_leading_seed():
  """Dense block writes must stay aligned when the FIRST run of a hop
  block has every edge masked (a zero-out-degree seed): its target
  reads -1, so a base derived from min(valid tgt) alone would shift the
  whole block (round-4 regression). Seed 0 is isolated here."""
  import jax
  from graphlearn_tpu.models import train as train_lib
  rng = np.random.default_rng(3)
  n = 200
  rows = rng.integers(1, n, 2000)      # node 0 has NO out-edges
  cols = rng.integers(1, n, 2000)
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 8)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 3, n))
  # seed block LEADS with the isolated node (seeds dedup ascending, so
  # node 0 is run 0 of hop 0)
  seeds = np.array([0, 5, 9, 13, 21, 34, 55, 89])
  loader = glt.loader.NeighborLoader(ds, [3, 2], seeds, batch_size=8,
                                     seed=0, dedup='map')
  b = train_lib.batch_to_dict(next(iter(loader)))
  no, eo = train_lib.merge_hop_offsets(8, [3, 2])
  for seg, dense in (
      (glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2,
                            hop_node_offsets=no, hop_edge_offsets=eo),
       glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2,
                            hop_node_offsets=no, hop_edge_offsets=eo,
                            merge_dense=True, fanouts=(3, 2))),
      (glt.models.GAT(hidden_dim=8, out_dim=3, num_layers=2, heads=2,
                      hop_node_offsets=no, hop_edge_offsets=eo),
       glt.models.GAT(hidden_dim=8, out_dim=3, num_layers=2, heads=2,
                      hop_node_offsets=no, hop_edge_offsets=eo,
                      merge_dense=True, fanouts=(3, 2)))):
    params = seg.init(jax.random.PRNGKey(0), b['x'], b['edge_index'],
                      b['edge_mask'])
    out_seg = np.asarray(seg.apply(params, b['x'], b['edge_index'],
                                   b['edge_mask']))
    out_dense = np.asarray(dense.apply(params, b['x'], b['edge_index'],
                                       b['edge_mask']))
    nseed = int(b['num_seed_nodes'])
    np.testing.assert_allclose(out_seg[:nseed], out_dense[:nseed],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # tier-1 budget: hgt_tree_dense variant stays tier-1
def test_tree_dense_hetero_matches_segment():
  """TreeHeteroConv's typed dense k-run aggregation == HeteroConv over
  per-etype segment convs on hetero tree batches (seed logits), for
  both SAGE and GAT convs, with the segment model's params remapped
  into the dense layout. The config exercises the hard layout cases:
  TWO etypes appending to the same type's buffer within one hop
  (cites + writes -> paper) and a LEAF-ONLY node type that vanishes
  from x_dict after layer 0 (topic)."""
  import jax
  CITES = ('paper', 'cites', 'paper')
  WRITES = ('author', 'writes', 'paper')
  REV = ('paper', 'rev_writes', 'author')
  TAG = ('paper', 'tags', 'topic')
  rng = np.random.default_rng(2)
  n_p, n_a, n_t = 100, 60, 20
  edges = {
      CITES: np.stack([rng.integers(0, n_p, 600),
                       rng.integers(0, n_p, 600)]),
      WRITES: np.stack([rng.integers(0, n_a, 300),
                        rng.integers(0, n_p, 300)]),
      REV: np.stack([rng.integers(0, n_p, 300),
                     rng.integers(0, n_a, 300)]),
      TAG: np.stack([rng.integers(0, n_p, 200),
                     rng.integers(0, n_t, 200)]),
  }
  nn_of = {'paper': n_p, 'author': n_a, 'topic': n_t}
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph(edges, graph_mode='CPU',
                num_nodes={et: nn_of[et[0]] for et in edges})
  ds.init_node_features(
      {t: rng.standard_normal((n, 6)).astype(np.float32)
       for t, n in nn_of.items()})
  ds.init_node_labels({'paper': rng.integers(0, 3, n_p)})
  fan = {CITES: [2, 2], WRITES: [2, 1], REV: [2, 1], TAG: [1, 0]}
  loader = glt.loader.NeighborLoader(ds, fan, ('paper', np.arange(n_p)),
                                     batch_size=4, seed=0, dedup='tree')
  b = next(iter(loader))
  x = {t: np.asarray(v) for t, v in b.x.items()}
  ei = {et: np.asarray(v) for et, v in b.edge_index.items()}
  em = {et: np.asarray(v) for et, v in b.edge_mask.items()}
  no_l, eo_l = glt.sampler.hetero_tree_layout({'paper': 4}, tuple(fan),
                                              fan)
  recs, no, eo = glt.sampler.hetero_tree_blocks({'paper': 4},
                                                tuple(fan), fan)
  assert {t: tuple(v) for t, v in no_l.items()} == dict(no)
  assert eo_l == eo
  # the canonical plan must be caller-order-independent
  recs_shuffled, _, _ = glt.sampler.hetero_tree_blocks(
      {'paper': 4}, tuple(reversed(list(fan))), fan)
  assert recs == recs_shuffled
  rev_et = tuple(glt.typing.reverse_edge_type(et) for et in fan)

  def remap(ps, conv, num_layers=2):
    src = ps['params']
    cls = 'SAGEConv' if conv == 'sage' else 'GATConv'
    newp = {k: v for k, v in src.items()
            if not k.startswith(cls + '_')}
    idx = 0
    # types alive after layer 0 = message targets (leaf-only types drop)
    alive = {r['key_t'] for rr in recs for r in rr}
    for i in range(num_layers):
      present = {r['et'] for rr in recs[:num_layers - i] for r in rr}
      het = {}
      for et_msg in rev_et:
        stored = glt.typing.reverse_edge_type(et_msg)
        # flax numbers modules by USE: HeteroConv skips a conv whose
        # src/dst type is absent from this layer's input, and skipped
        # convs consume no name index
        called = i == 0 or (et_msg[0] in alive and et_msg[2] in alive)
        if not called:
          continue
        sub = src[f'{cls}_{idx}']
        idx += 1
        if stored not in present:
          continue
        ename = '__'.join(stored)
        if conv == 'sage':
          het[f'lin_self_{ename}'] = sub['lin_self']
          het[f'lin_nbr_{ename}'] = sub['lin_nbr']
        else:
          het[f'lin_{ename}'] = sub['lin']
          het[f'att_src_{ename}'] = sub['att_src']
          het[f'att_dst_{ename}'] = sub['att_dst']
      newp[f'hetero{i}'] = het
    return {'params': newp}

  for conv in ('sage', 'gat'):
    kw = dict(etypes=rev_et, hidden_dim=8, out_dim=3, conv=conv,
              heads=2, num_layers=2, out_ntype='paper',
              hop_node_offsets=no, hop_edge_offsets=eo)
    seg = glt.models.RGNN(**kw)
    dense = glt.models.RGNN(**kw, tree_dense=True, tree_records=recs)
    ps = jax.jit(seg.init)(jax.random.PRNGKey(0), x, ei, em)
    pd = remap(ps, conv)
    o_seg = np.asarray(jax.jit(seg.apply)(ps, x, ei, em))
    o_dense = np.asarray(jax.jit(dense.apply)(pd, x, ei, em))
    nseed = int(np.asarray(b.num_sampled_nodes['paper'])[0])
    np.testing.assert_allclose(o_seg[:nseed], o_dense[:nseed],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # tier-1 budget (PR 16): tree-dense coverage rides on
# test_tree_dense_gat_matches_segment; HGT rides on the merge-dense rep
def test_hgt_tree_dense_matches_segment():
  """HGTConv's dense k-run typed attention (tree_records) == the
  segment-softmax path on hetero tree batches — SAME params (the dense
  path is a mode of the same conv), seed logits compared."""
  import jax
  ET1, ET2 = ('u', 'to', 'v'), ('v', 'back', 'u')
  rng = np.random.default_rng(5)
  nu, nv = 90, 70
  e1 = np.stack([rng.integers(0, nu, 500), rng.integers(0, nv, 500)])
  e2 = np.stack([rng.integers(0, nv, 400), rng.integers(0, nu, 400)])
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph({ET1: e1, ET2: e2}, graph_mode='CPU',
                num_nodes={ET1: nu, ET2: nv})
  ds.init_node_features(
      {'u': rng.standard_normal((nu, 8)).astype(np.float32),
       'v': rng.standard_normal((nv, 8)).astype(np.float32)})
  ds.init_node_labels({'u': rng.integers(0, 3, nu)})
  fan = {ET1: [3, 2], ET2: [2, 2]}
  loader = glt.loader.NeighborLoader(ds, fan, ('u', np.arange(nu)),
                                     batch_size=8, seed=0, dedup='tree')
  b = next(iter(loader))
  x = {t: np.asarray(v) for t, v in b.x.items()}
  ei = {et: np.asarray(v) for et, v in b.edge_index.items()}
  em = {et: np.asarray(v) for et, v in b.edge_mask.items()}
  recs, no, eo = glt.sampler.hetero_tree_blocks({'u': 8}, tuple(fan),
                                                fan)
  ntypes = ('u', 'v')
  etypes = tuple(sorted(ei))          # message-flow types, batch keys
  from graphlearn_tpu.models import HGT
  kw = dict(ntypes=ntypes, etypes=etypes, hidden_dim=8, out_dim=3,
            heads=2, num_layers=2, out_ntype='u',
            hop_node_offsets=no, hop_edge_offsets=eo)
  seg = HGT(**kw)
  dense = HGT(**kw, tree_records=recs)
  params = jax.jit(seg.init)(jax.random.PRNGKey(0), x, ei, em)
  o_seg = np.asarray(jax.jit(seg.apply)(params, x, ei, em))
  o_dense = np.asarray(jax.jit(dense.apply)(params, x, ei, em))
  nseed = int(np.asarray(b.num_sampled_nodes['u'])[0])
  np.testing.assert_allclose(o_seg[:nseed], o_dense[:nseed],
                             rtol=2e-4, atol=2e-4)


# tier-1 budget (ROADMAP 870s): the heaviest hetero equivalence
# variants run under the slow marker; tier-1 keeps the typed-dense
# (test_tree_dense_hetero_matches_segment) and typed-merge
# (test_hgt_merge_dense_matches_segment[True]) representatives
@pytest.mark.slow
@pytest.mark.parametrize('use_caps', [True, False])
def test_merge_dense_hetero_matches_segment(use_caps):
  """TreeHeteroConv(mode='merge') — dense k-run typed aggregation over
  exact-dedup hetero batches — matches HeteroConv over per-etype
  segment convs (seed logits), SAGE and GAT, with the segment params
  remapped into the dense layout. Exercises multi-etype same-target
  hops (cites + writes -> paper), a leaf-only type (topic), and BOTH
  calibrated caps (clamped buffers, dynamic packing) and the uncapped
  merge layout (the engine's cross-part frontier compaction must keep
  run bases arithmetic in both)."""
  import jax
  CITES = ('paper', 'cites', 'paper')
  WRITES = ('author', 'writes', 'paper')
  REV = ('paper', 'rev_writes', 'author')
  TAG = ('paper', 'tags', 'topic')
  rng = np.random.default_rng(4)
  n_p, n_a, n_t = 120, 70, 20
  edges = {
      CITES: np.stack([rng.integers(0, n_p, 700),
                       rng.integers(0, n_p, 700)]),
      WRITES: np.stack([rng.integers(0, n_a, 350),
                        rng.integers(0, n_p, 350)]),
      REV: np.stack([rng.integers(0, n_p, 350),
                     rng.integers(0, n_a, 350)]),
      TAG: np.stack([rng.integers(0, n_p, 240),
                     rng.integers(0, n_t, 240)]),
  }
  nn_of = {'paper': n_p, 'author': n_a, 'topic': n_t}
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph(edges, graph_mode='CPU',
                num_nodes={et: nn_of[et[0]] for et in edges})
  ds.init_node_features(
      {t: rng.standard_normal((n, 6)).astype(np.float32)
       for t, n in nn_of.items()})
  ds.init_node_labels({'paper': rng.integers(0, 3, n_p)})
  fan = {CITES: [2, 2], WRITES: [2, 1], REV: [2, 1], TAG: [1, 0]}
  caps = None
  if use_caps:
    caps = glt.sampler.estimate_hetero_frontier_caps(
        ds.graph, fan, {'paper': 8}, num_probes=6, slack=1.5, multiple=4)
  loader = glt.loader.NeighborLoader(ds, fan, ('paper', np.arange(n_p)),
                                     batch_size=8, seed=0, dedup='merge',
                                     frontier_caps=caps)
  recs, no, eo = glt.sampler.hetero_tree_blocks(
      {'paper': 8}, tuple(fan), fan, etype_caps=caps)
  if use_caps:
    # calibrated layout genuinely shrinks vs the worst-case plan
    _, no_full, _ = glt.sampler.hetero_tree_blocks({'paper': 8},
                                                   tuple(fan), fan)
    assert no['paper'][-1] < no_full['paper'][-1]
  rev_et = tuple(glt.typing.reverse_edge_type(et) for et in fan)

  def remap(ps, conv, num_layers=2):
    src = ps['params']
    cls = 'SAGEConv' if conv == 'sage' else 'GATConv'
    newp = {k: v for k, v in src.items()
            if not k.startswith(cls + '_')}
    idx = 0
    alive = {r['key_t'] for rr in recs for r in rr}
    for i in range(num_layers):
      present = {r['et'] for rr in recs[:num_layers - i] for r in rr}
      het = {}
      for et_msg in rev_et:
        stored = glt.typing.reverse_edge_type(et_msg)
        called = i == 0 or (et_msg[0] in alive and et_msg[2] in alive)
        if not called:
          continue
        sub = src[f'{cls}_{idx}']
        idx += 1
        if stored not in present:
          continue
        ename = '__'.join(stored)
        if conv == 'sage':
          het[f'lin_self_{ename}'] = sub['lin_self']
          het[f'lin_nbr_{ename}'] = sub['lin_nbr']
        else:
          het[f'lin_{ename}'] = sub['lin']
          het[f'att_src_{ename}'] = sub['att_src']
          het[f'att_dst_{ename}'] = sub['att_dst']
      newp[f'hetero{i}'] = het
    return {'params': newp}

  for bi, b in enumerate(loader):
    if bi >= 2:
      break
    x = {t: np.asarray(v) for t, v in b.x.items()}
    ei = {et: np.asarray(v) for et, v in b.edge_index.items()}
    em = {et: np.asarray(v) for et, v in b.edge_mask.items()}
    for conv in ('sage', 'gat'):
      kw = dict(etypes=rev_et, hidden_dim=8, out_dim=3, conv=conv,
                heads=2, num_layers=2, out_ntype='paper',
                hop_node_offsets=no, hop_edge_offsets=eo)
      seg = glt.models.RGNN(**kw)
      dense = glt.models.RGNN(**kw, merge_dense=True, tree_records=recs)
      ps = jax.jit(seg.init)(jax.random.PRNGKey(0), x, ei, em)
      pd = remap(ps, conv)
      o_seg = np.asarray(jax.jit(seg.apply)(ps, x, ei, em))
      o_dense = np.asarray(jax.jit(dense.apply)(pd, x, ei, em))
      nseed = int(np.asarray(b.num_sampled_nodes['paper'])[0])
      np.testing.assert_allclose(o_seg[:nseed], o_dense[:nseed],
                                 rtol=2e-4, atol=2e-4)


def _run_mean_case(f, k, fd=16, n=53):
  """Rows table, flat f-major src with -1 padding and a [f, k] mask that
  holds an all-masked run, a short run (degree < k) and a full run."""
  rng = np.random.default_rng(31 * f + k)
  x = rng.standard_normal((n, fd)).astype(np.float32)
  m = rng.random((f, k)) < 0.7
  m[0] = False                      # all-masked run (zero-degree parent)
  m[1] = np.arange(k) < 2           # short run: degree 2 < k
  m[2] = True                       # full run
  src = np.where(m, rng.integers(0, n, (f, k)), -1).astype(np.int32)
  return x, src.reshape(-1), m


@pytest.mark.parametrize('f', [37, 128])
@pytest.mark.parametrize('k', [5, 10, 15])
def test_gathered_run_mean_matches_reshape(k, f):
  """The k-major gathered run mean of the merge convs == the [f, k, F]
  reshape mean it replaces, in value and in the gradient w.r.t. the rows
  table (f = 37 is no multiple of 8: the [k, f, F] view may cost a pad,
  never a value)."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.models import models as M
  x, src, m = _run_mean_case(f, k)
  x, src, m = jnp.asarray(x), jnp.asarray(src), jnp.asarray(m)
  w = jnp.asarray(np.random.default_rng(1).standard_normal(
      (f, x.shape[1])).astype(np.float32))

  def ref(x):
    return M._masked_run_mean(x[jnp.maximum(src, 0)].reshape(f, k, -1), m)

  def new(x):
    return M._gathered_run_mean(x, src, m, k)

  out_ref, out_new = np.asarray(ref(x)), np.asarray(new(x))
  np.testing.assert_allclose(out_new, out_ref, rtol=1e-6, atol=1e-6)
  assert not out_new[0].any()       # the all-masked run reads 0
  np.testing.assert_allclose(       # the short run: 2 slots, divisor 2
      out_new[1], np.asarray(x)[np.asarray(src)[k:k + 2]].mean(0),
      rtol=1e-6, atol=1e-6)
  g_ref = jax.grad(lambda x: (ref(x) * w).sum())(x)
  g_new = jax.grad(lambda x: (new(x) * w).sum())(x)
  np.testing.assert_allclose(np.asarray(g_new), np.asarray(g_ref),
                             rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('k', [5, 10, 15])
def test_flat_run_mean_grad_matches_segment(k):
  """jit(grad) of the slice-fed tree convs' run mean w.r.t. the rows
  equals a segment mean's, with an all-masked and a short run: the
  backward of TreeSAGEConv / TreeHeteroConv._sage_et, which a
  forward-only equivalence test does not see."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.models import models as M
  f = 37
  x, src, m = _run_mean_case(f, k)
  rows = jnp.asarray(x[np.maximum(src, 0)])           # flat [f*k, F]
  m = jnp.asarray(m)
  w = jnp.asarray(np.random.default_rng(2).standard_normal(
      (f, x.shape[1])).astype(np.float32))
  seg = jnp.repeat(jnp.arange(f), k)

  def ref(rows):
    mf = m.reshape(-1).astype(rows.dtype)
    s = jax.ops.segment_sum(rows * mf[:, None], seg, f)
    cnt = jnp.maximum(jax.ops.segment_sum(mf, seg, f), 1)
    return (s / cnt[:, None] * w).sum()

  def new(rows):
    return (M._masked_flat_run_mean(rows, m, k) * w).sum()

  np.testing.assert_allclose(float(jax.jit(new)(rows)),
                             float(jax.jit(ref)(rows)), rtol=1e-5)
  g_ref = np.asarray(jax.jit(jax.grad(ref))(rows))
  g_new = np.asarray(jax.jit(jax.grad(new))(rows))
  assert not g_new[:k].any()          # the all-masked run
  assert g_new[k:k + 2].any() and not g_new[k + 2:2 * k].any()  # short
  np.testing.assert_allclose(g_new, g_ref, rtol=1e-5, atol=1e-6)


def _jaxpr_shapes(jaxpr):
  """Shapes of every value a jaxpr computes, sub-jaxprs included."""
  import jax
  for eqn in jaxpr.eqns:
    for v in eqn.outvars:
      yield tuple(getattr(v.aval, 'shape', ()))
    for sub in jax.core.jaxprs_in_params(eqn.params):
      yield from _jaxpr_shapes(sub)


def test_merge_sage_conv_holds_no_run_view():
  """Structural guard: neither MergeSAGEConv's forward nor its backward
  holds a rank-3 (f, k, F) value — the view that put k on the padded
  sublane axis and cost a relayout of every gathered row — so it cannot
  come back unseen. The walker is checked on the old form first."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.models import models as M
  hops = ((16, 5), (80, 3))         # (f, k): no (f, k) equals a (k, f)
  n, fd, e = 120, 24, 16 * 5 + 80 * 3
  x = jnp.ones((n, fd), jnp.float32)
  ei = jnp.zeros((2, e), jnp.int32)
  em = jnp.ones((e,), bool)
  conv = M.MergeSAGEConv(out_dim=8, edge_offsets=(80, e), fanouts=(5, 3))
  params = conv.init(jax.random.PRNGKey(0), x, ei, em)

  def loss(params, x):
    return conv.apply(params, x, ei, em).sum()

  def run_views(fn, *args):
    shapes = set(_jaxpr_shapes(jax.make_jaxpr(fn)(*args).jaxpr))
    rows = {s for s in shapes if len(s) == 3 and s[2] == fd}  # not masks
    return ({s for s in rows if s[:2] in hops},
            {s for s in rows if s[:2] in tuple((k, f) for f, k in hops)})

  def old(x):
    return M._masked_flat_run_mean(x[ei[0, :80]], em[:80].reshape(16, 5),
                                   5).sum()

  assert run_views(jax.grad(old), x)[0] == {(16, 5, fd)}
  for fn in (loss, jax.grad(loss, argnums=(0, 1))):
    f_major, k_major = run_views(fn, params, x)
    assert not f_major, f_major
    assert k_major == {(5, 16, fd), (3, 80, fd)}


def _run_softmax_case(f, k, h, seed):
  """Logits [f, k, h] and a mask [f, k] with an underflow-prone run (3:
  every valid logit near -2000 after scaling), an all-masked run (5) and
  a run of degree 2 < k (7)."""
  rng = np.random.default_rng(seed)
  e = rng.standard_normal((f, k, h)).astype(np.float32) * 10
  e[3] -= 200.0                       # underflow-prone run
  m = rng.random((f, k)) < 0.6
  m[3, 0] = m[3, 1] = True
  m[5] = False                        # all-masked run
  m[7] = np.arange(k) < 2             # short run: degree 2 < k
  return e, m


def _segment_softmax(e, m, seg, f):
  """Segment softmax over the valid slots of flat [f*k, h] logits: the
  reference the run kernels replace (GATConv's stabilization)."""
  import jax
  import jax.numpy as jnp
  le = jax.nn.leaky_relu(e, 0.2)
  mf = m[:, None]
  mx = jax.ops.segment_max(jnp.where(mf, le, -jnp.inf), seg, f)
  ex = jnp.where(mf, jnp.exp(le - jnp.where(jnp.isfinite(mx), mx,
                                            0.0)[seg]), 0.0)
  den = jnp.maximum(jax.ops.segment_sum(ex, seg, f), 1e-9)
  return ex / den[seg]


@pytest.mark.parametrize('k', [5, 10, 15])
@pytest.mark.parametrize('axis', [1, 0])
def test_run_softmax_grad_matches_segment(axis, k):
  """jit(grad) of the run softmax w.r.t. the logits — runs on axis 1
  (the slice-fed tree convs) and k-major on axis 0 (the merge convs) —
  equals a segment softmax's (segment max / sum over the valid slots):
  the all-masked run takes no gradient and the underflow-prone run keeps
  a finite one."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.models import models as M
  f, h = 23, 2
  e, m = _run_softmax_case(f, k, h, 7 + k)
  w = jnp.asarray(np.random.default_rng(k).standard_normal(
      (f, k, h)).astype(np.float32))
  e, m = jnp.asarray(e), jnp.asarray(m)
  seg = jnp.repeat(jnp.arange(f), k)

  def ref(e):
    return (_segment_softmax(e.reshape(f * k, h), m.reshape(f * k), seg,
                             f).reshape(f, k, h) * w).sum()

  def new(e):
    if axis == 0:   # the same runs, k-major
      a = M._masked_run_softmax(e.transpose(1, 0, 2), m.T, jnp.float32,
                                0.2, axis=0).transpose(1, 0, 2)
    else:
      a = M._masked_run_softmax(e, m, jnp.float32, 0.2)
    return (a * w).sum()

  np.testing.assert_allclose(float(jax.jit(new)(e)), float(jax.jit(ref)(e)),
                             rtol=1e-5)
  g_ref = np.asarray(jax.jit(jax.grad(ref))(e))
  g_new = np.asarray(jax.jit(jax.grad(new))(e))
  assert np.isfinite(g_new).all()
  assert not g_new[5].any()           # the all-masked run
  assert g_new[3][np.asarray(m)[3]].any()   # the underflow-prone run
  assert not g_new[7, 2:].any()       # the short run's masked slots
  np.testing.assert_allclose(g_new, g_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('k', [5, 10, 15])
def test_run_softmax_axis0_equals_axis1(k):
  """The k-major run softmax is the axis-1 one on transposed operands, to
  the bit: max, exp and sum run over the same k values in the same
  order, whichever axis holds them."""
  import jax.numpy as jnp
  from graphlearn_tpu.models import models as M
  e, m = _run_softmax_case(23, k, 4, 11 + k)
  e, m = jnp.asarray(e), jnp.asarray(m)
  a1 = np.asarray(M._masked_run_softmax(e, m, jnp.float32, 0.2))
  a0 = np.asarray(M._masked_run_softmax(e.transpose(1, 0, 2), m.T,
                                        jnp.float32, 0.2, axis=0))
  np.testing.assert_array_equal(a0.transpose(1, 0, 2), a1)
  assert not a1[5].any()                        # all-masked: weights 0
  np.testing.assert_allclose(a1[3].sum(0), 1.0, rtol=1e-6)   # underflow
  np.testing.assert_allclose(a1[7, :2].sum(0), 1.0, rtol=1e-6)  # short


@pytest.mark.parametrize('f', [37, 128])
@pytest.mark.parametrize('k', [5, 10, 15])
def test_gat_runs_k_major_matches_segment(k, f):
  """``_gat_runs`` (children gathered k-major, weights widened by the
  0/1 head matrix) against a segment-softmax GAT aggregation over the
  same edges: forward and jit(grad) w.r.t. the projected rows, the alpha
  table and the parents' alphas, with an underflow-prone, an all-masked
  and a short run (f = 37 is no multiple of 8)."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.models import models as M
  heads, hd, n = 4, 8, 61
  rng = np.random.default_rng(100 + k + f)
  _, m = _run_softmax_case(f, k, heads, 3 + k)
  src = rng.integers(0, n, (f, k)).astype(np.int32)
  w = rng.standard_normal((n, heads * hd)).astype(np.float32)
  a_src = rng.standard_normal((n, heads)).astype(np.float32) * 10
  a_par = rng.standard_normal((f, heads)).astype(np.float32) * 10
  a_par[3] -= 2000.0                  # underflow-prone run
  cot = jnp.asarray(rng.standard_normal((f, heads * hd)).astype(np.float32))
  w, a_src, a_par = jnp.asarray(w), jnp.asarray(a_src), jnp.asarray(a_par)
  src_f, mj = jnp.asarray(src.reshape(-1)), jnp.asarray(m)
  seg = jnp.repeat(jnp.arange(f), k)

  def ref(w, a_src, a_par):
    e = a_src[src_f] + a_par[seg]                          # [f*k, H]
    attn = _segment_softmax(e, mj.reshape(-1), seg, f)
    msgs = w[src_f].reshape(f * k, heads, hd) * attn[:, :, None]
    return jax.ops.segment_sum(msgs, seg, f).reshape(f, heads * hd)

  def new(w, a_src, a_par):
    return M._gat_runs(w, a_src, a_par, mj, src_f, heads, hd, 0.2)

  out_ref = np.asarray(jax.jit(ref)(w, a_src, a_par))
  out_new = np.asarray(jax.jit(new)(w, a_src, a_par))
  np.testing.assert_allclose(out_new, out_ref, rtol=1e-5, atol=1e-6)
  assert not out_new[5].any()         # the all-masked run reads 0
  assert np.isfinite(out_new).all() and out_new[3].any()   # underflow
  g_ref = jax.jit(jax.grad(lambda *a: (ref(*a) * cot).sum(), (0, 1, 2)))(
      w, a_src, a_par)
  g_new = jax.jit(jax.grad(lambda *a: (new(*a) * cot).sum(), (0, 1, 2)))(
      w, a_src, a_par)
  for gn, gr in zip(g_new, g_ref):
    assert np.isfinite(np.asarray(gn)).all()
    np.testing.assert_allclose(np.asarray(gn), np.asarray(gr), rtol=1e-4,
                               atol=1e-5)
  assert not np.asarray(g_new[2])[5].any()   # all-masked: no gradient


def test_merge_gat_conv_holds_no_head_view():
  """Structural guard: MergeGATConv's forward and backward hold no
  rank-4 value and no f-major (f, k, .) run view — the [f, k, H, D] view
  of the gathered messages (H = 4 on the sublane axis of every row) and
  the [f, k, H] alphas cannot come back unseen. The walker is checked on
  the old form first."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.models import models as M
  hops = ((16, 5), (80, 3))         # (f, k): no (f, k) equals a (k, f)
  n, fd, e, heads, hd = 120, 24, 16 * 5 + 80 * 3, 4, 8
  x = jnp.ones((n, fd), jnp.float32)
  ei = jnp.zeros((2, e), jnp.int32)
  em = jnp.ones((e,), bool)
  conv = M.MergeGATConv(out_dim=hd, heads=heads, edge_offsets=(80, e),
                        fanouts=(5, 3))
  params = conv.init(jax.random.PRNGKey(0), x, ei, em)

  def loss(params, x):
    return conv.apply(params, x, ei, em).sum()

  def views(fn, *args):
    shapes = set(_jaxpr_shapes(jax.make_jaxpr(fn)(*args).jaxpr))
    return ({s for s in shapes if len(s) >= 4},
            {s for s in shapes if len(s) == 3 and s[:2] in hops})

  def old(w):
    msgs = w[ei[0, :80]].reshape(16, 5, heads, hd)
    return (msgs * jnp.ones((16, 5, heads))[..., None]).sum()

  assert views(jax.grad(old), jnp.ones((n, heads * hd)))[0]
  for fn in (loss, jax.grad(loss, argnums=(0, 1))):
    rank4, f_major = views(fn, params, x)
    assert not rank4, rank4
    assert not f_major, f_major


@pytest.mark.slow  # tier-1 budget (PR 19): HGT parity stays tier-1 via
# test_hgt_tree_dense_matches_segment and the SAGE merge-dense parity
# test covers the merge lane; the full suite runs both cap modes here
@pytest.mark.parametrize('use_caps', [True, False])
def test_hgt_merge_dense_matches_segment(use_caps):
  """HGT(merge_dense=True) — dense k-run typed attention on exact-dedup
  merge batches (calibrated caps and uncapped) — matches the segment
  softmax path with the SAME params (merge is a mode of the same
  conv), seed logits compared."""
  import jax
  ET1, ET2 = ('u', 'to', 'v'), ('v', 'back', 'u')
  rng = np.random.default_rng(6)
  nu, nv = 90, 70
  e1 = np.stack([rng.integers(0, nu, 500), rng.integers(0, nv, 500)])
  e2 = np.stack([rng.integers(0, nv, 400), rng.integers(0, nu, 400)])
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph({ET1: e1, ET2: e2}, graph_mode='CPU',
                num_nodes={ET1: nu, ET2: nv})
  ds.init_node_features(
      {'u': rng.standard_normal((nu, 8)).astype(np.float32),
       'v': rng.standard_normal((nv, 8)).astype(np.float32)})
  ds.init_node_labels({'u': rng.integers(0, 3, nu)})
  fan = {ET1: [3, 2], ET2: [2, 2]}
  caps = None
  if use_caps:
    caps = glt.sampler.estimate_hetero_frontier_caps(
        ds.graph, fan, {'u': 8}, num_probes=6, slack=1.5, multiple=4)
  loader = glt.loader.NeighborLoader(ds, fan, ('u', np.arange(nu)),
                                     batch_size=8, seed=0, dedup='merge',
                                     frontier_caps=caps)
  recs, no, eo = glt.sampler.hetero_tree_blocks({'u': 8}, tuple(fan),
                                                fan, etype_caps=caps)
  ntypes = ('u', 'v')
  from graphlearn_tpu.models import HGT
  params = None
  for bi, b in enumerate(loader):
    if bi >= 2:
      break
    x = {t: np.asarray(v) for t, v in b.x.items()}
    ei = {et: np.asarray(v) for et, v in b.edge_index.items()}
    em = {et: np.asarray(v) for et, v in b.edge_mask.items()}
    etypes = tuple(sorted(ei))
    kw = dict(ntypes=ntypes, etypes=etypes, hidden_dim=8, out_dim=3,
              heads=2, num_layers=2, out_ntype='u',
              hop_node_offsets=no, hop_edge_offsets=eo)
    seg = HGT(**kw)
    dense = HGT(**kw, tree_records=recs, merge_dense=True)
    if params is None:
      params = jax.jit(seg.init)(jax.random.PRNGKey(0), x, ei, em)
    o_seg = np.asarray(jax.jit(seg.apply)(params, x, ei, em))
    o_dense = np.asarray(jax.jit(dense.apply)(params, x, ei, em))
    nseed = int(np.asarray(b.num_sampled_nodes['u'])[0])
    np.testing.assert_allclose(o_seg[:nseed], o_dense[:nseed],
                               rtol=2e-4, atol=2e-4)
