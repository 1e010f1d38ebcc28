"""Loader tests, mirroring the reference's loader coverage
(test_link_loader.py, neighbor loader paths in test_neighbor_sampler.py)."""
import numpy as np
import pytest

import graphlearn_tpu as glt


def make_dataset(n=16, f=4):
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(n), 3)
  cols = (rows + rng.integers(1, n, rows.shape[0])) % n
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=n)
  feat = np.arange(n, dtype=np.float32)[:, None] * np.ones((1, f),
                                                           np.float32)
  ds.init_node_features(feat, sort_func=glt.data.sort_by_in_degree,
                        split_ratio=0.5)
  ds.init_node_labels(np.arange(n) % 3)
  return ds, feat


def test_neighbor_loader_batches():
  ds, feat = make_dataset()
  loader = glt.loader.NeighborLoader(ds, [2, 2], np.arange(16),
                                     batch_size=4, shuffle=True, seed=0)
  assert len(loader) == 4
  seen = []
  for batch in loader:
    assert batch.batch_size == 4
    node = np.asarray(batch.node)
    n = int(batch.num_nodes)
    # features/labels aligned to node list
    x = np.asarray(batch.x)
    y = np.asarray(batch.y)
    np.testing.assert_allclose(x[:n], feat[node[:n]])
    np.testing.assert_array_equal(y[:n], node[:n] % 3)
    seen.extend(node[:4].tolist())
  assert sorted(seen) == list(range(16))


def test_neighbor_loader_static_shapes():
  ds, _ = make_dataset()
  loader = glt.loader.NeighborLoader(ds, [2], np.arange(10), batch_size=4)
  shapes = {tuple(np.asarray(b.node).shape) for b in loader}
  # padded: every batch (incl. the short last one) has identical shape
  assert len(shapes) == 1


def test_link_neighbor_loader_binary():
  ds, _ = make_dataset()
  g = ds.get_graph()
  row, col = g.topo.to_coo()
  loader = glt.loader.LinkNeighborLoader(
      ds, [2], np.stack([row[:8], col[:8]]),
      neg_sampling=glt.sampler.NegativeSampling('binary', 1),
      batch_size=4, seed=1)
  batches = list(loader)
  assert len(batches) == 2
  b = batches[0]
  eli = np.asarray(b.metadata['edge_label_index'])
  label = np.asarray(b.metadata['edge_label'])
  assert eli.shape[1] == label.shape[0] == 8  # 4 pos + 4 neg
  assert label[:4].sum() == 4 and label[4:].sum() == 0


def test_subgraph_loader():
  ds, _ = make_dataset()
  loader = glt.loader.SubGraphLoader(ds, [2], np.arange(8), batch_size=4)
  for b in loader:
    mapping = np.asarray(b.metadata['mapping'])
    node = np.asarray(b.node)
    assert (mapping >= 0).all()
    # seeds are locatable in the node list
    np.testing.assert_array_equal(node[mapping], np.asarray(b.batch))


def test_to_pyg_bridge():
  try:
    import torch_geometric  # noqa: F401
  except ImportError:
    import pytest
    pytest.skip('torch_geometric not installed')
  ds, _ = make_dataset()
  loader = glt.loader.NeighborLoader(ds, [2], np.arange(8), batch_size=4)
  b = next(iter(loader))
  pyg = b.to_pyg()
  assert pyg.edge_index.shape[0] == 2
  assert pyg.batch_size == 4


def make_hetero_dataset():
  ub = np.array([[0, 0, 1, 2, 2, 3], [0, 1, 2, 3, 0, 1]])
  bu = ub[::-1].copy()
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph({('user', 'buys', 'item'): ub,
                 ('item', 'rev_buys', 'user'): bu},
                graph_mode='CPU',
                num_nodes={('user', 'buys', 'item'): 4,
                           ('item', 'rev_buys', 'user'): 4})
  ds.init_node_features({'user': np.eye(4, dtype=np.float32),
                         'item': np.eye(4, dtype=np.float32) * 2})
  return ds, ub


def test_hetero_link_neighbor_loader_binary():
  ds, ub = make_hetero_dataset()
  loader = glt.loader.LinkNeighborLoader(
      ds, [2, 2], (('user', 'buys', 'item'), ub),
      neg_sampling=glt.sampler.NegativeSampling('binary', 1),
      batch_size=3, seed=0)
  batches = list(loader)
  assert len(batches) == 2
  b = batches[0]
  eli = np.asarray(b.metadata['edge_label_index'])
  label = np.asarray(b.metadata['edge_label'])
  assert eli.shape == (2, 6) and label.shape == (6,)
  assert label[:3].sum() == 3 and label[3:].sum() == 0
  pos = {(int(r), int(c)) for r, c in zip(ub[0], ub[1])}
  user_nodes = np.asarray(b.node['user'])
  item_nodes = np.asarray(b.node['item'])
  for j in range(3):  # positives decode to real edges
    u = int(user_nodes[eli[0, j]])
    i = int(item_nodes[eli[1, j]])
    assert (u, i) in pos
  # features collected per type
  assert b.x['user'].shape[1] == 4


def test_hetero_link_neighbor_loader_triplet():
  ds, ub = make_hetero_dataset()
  loader = glt.loader.LinkNeighborLoader(
      ds, [2], (('user', 'buys', 'item'), ub),
      neg_sampling=glt.sampler.NegativeSampling('triplet', 2),
      batch_size=3, seed=1)
  b = next(iter(loader))
  assert np.asarray(b.metadata['src_index']).shape == (3,)
  assert np.asarray(b.metadata['dst_pos_index']).shape == (3,)
  assert np.asarray(b.metadata['dst_neg_index']).shape == (6,)
  user_nodes = np.asarray(b.node['user'])
  src = user_nodes[np.asarray(b.metadata['src_index'])]
  np.testing.assert_array_equal(src, ub[0][:3])


def test_checkpoint_resume_training():
  """CheckpointManager round-trip: train 2 epochs + save, then restore
  into a fresh state/loader and verify (a) arrays match exactly, (b) the
  restored loader replays the SAME remaining permutation sequence as the
  uninterrupted run (epoch-boundary resume contract)."""
  import tempfile
  import jax
  import numpy as np
  import graphlearn_tpu as glt
  from graphlearn_tpu.models import GraphSAGE, train as train_lib

  rng = np.random.default_rng(0)
  n = 100
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rng.integers(0, n, 600),
                          rng.integers(0, n, 600)]),
                num_nodes=n, graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 8)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 3, n))

  def make_loader():
    return glt.loader.NeighborLoader(ds, [3, 2], np.arange(n),
                                     batch_size=16, shuffle=True,
                                     drop_last=True, seed=7)

  model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  loader = make_loader()
  first = train_lib.batch_to_dict(next(iter(loader)))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  step, _ = train_lib.make_train_step(model, tx, 3)
  for _ in range(2):
    for b in loader:
      state, loss, acc = step(state, train_lib.batch_to_dict(b))

  with tempfile.TemporaryDirectory() as d:
    mgr = glt.utils.CheckpointManager(d, max_to_keep=2)
    mgr.save(2, state, loader=loader, extra={'epoch': 2})
    # uninterrupted continuation: the next permutation the loader draws
    cont_perm = [np.asarray(b.node) for b in loader]

    # fresh process simulation: new loader + template state
    loader2 = make_loader()
    tmpl, _ = train_lib.create_train_state(model, jax.random.PRNGKey(1),
                                           first)
    restored, extra = mgr.restore(tmpl, loader=loader2)
    assert extra == {'epoch': 2}
    ra, sa = (jax.tree_util.tree_leaves(restored.params),
              jax.tree_util.tree_leaves(state.params))
    for r, s in zip(ra, sa):
      np.testing.assert_array_equal(np.asarray(r), np.asarray(s))
    resumed_perm = [np.asarray(b.node) for b in loader2]
    for a, b in zip(cont_perm, resumed_perm):
      np.testing.assert_array_equal(a, b)
    # retention: saving 2 more steps drops the oldest
    mgr.save(3, state)
    mgr.save(4, state)
    assert mgr.all_steps() == [3, 4]

    # restored state trains on
    s2 = restored
    for b in loader2:
      s2, loss, acc = step(s2, train_lib.batch_to_dict(b))
      break
    assert np.isfinite(float(loss))


def test_mid_epoch_resume_exact():
  """MID-EPOCH resume: snapshot after k batches of an epoch; a fresh
  loader restored from it must produce exactly the batches the
  uninterrupted run produced from k+1 on — including the rest of the
  current epoch AND the following epoch."""
  import numpy as np
  import graphlearn_tpu as glt

  rng = np.random.default_rng(1)
  n = 128
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rng.integers(0, n, 800),
                          rng.integers(0, n, 800)]),
                num_nodes=n, graph_mode='CPU')
  ds.init_node_features(rng.standard_normal((n, 4)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 3, n))

  def make_loader():
    return glt.loader.NeighborLoader(ds, [3, 2], np.arange(n),
                                     batch_size=16, shuffle=True,
                                     drop_last=True, seed=11)

  # uninterrupted reference run: one full epoch + snapshot point at k=3
  ref = make_loader()
  it = iter(ref)
  k = 3
  for _ in range(k):
    next(it)
  snap = ref.state_dict()
  remaining = [np.asarray(b.node) for b in it]          # rest of epoch
  next_epoch = [np.asarray(b.node) for b in ref]        # epoch 2

  res = make_loader()
  res.load_state_dict(snap)
  got = [np.asarray(b.node) for b in res]
  got2 = [np.asarray(b.node) for b in res]
  assert len(got) == len(remaining)
  for a, b in zip(remaining + next_epoch, got + got2):
    np.testing.assert_array_equal(a, b)

  # epoch-end snapshot: restore continues with the NEXT epoch (no
  # empty replay epoch)
  ref2 = make_loader()
  for _ in ref2:
    pass
  snap2 = ref2.state_dict()
  want = [np.asarray(b.node) for b in ref2]
  res2 = make_loader()
  res2.load_state_dict(snap2)
  got3 = [np.asarray(b.node) for b in res2]
  assert len(got3) == len(want)
  for a, b in zip(want, got3):
    np.testing.assert_array_equal(a, b)


def test_hetero_seed_labels_only():
  """seed_labels_only on the hetero path: y carries the input type's
  seed block only; values match the seed slots' labels."""
  ds, ub = make_hetero_dataset()
  ds.init_node_labels({'user': np.array([3, 1, 4, 1]),
                       'item': np.array([5, 9, 2, 6])})
  loader = glt.loader.NeighborLoader(
      ds, {('user', 'buys', 'item'): [2],
           ('item', 'rev_buys', 'user'): [2]},
      ('user', np.array([2, 0, 1])), batch_size=3, seed=0,
      seed_labels_only=True)
  b = next(iter(loader))
  assert set(b.y) == {'user'}
  got = np.asarray(b.y['user'])
  assert got.shape == (3,)
  node = np.asarray(b.node['user'])[:3]
  np.testing.assert_array_equal(got, np.array([3, 1, 4, 1])[node])


def test_checkpoint_link_loader():
  """Link loaders expose the same resume contract (batcher + sampler
  PRNG): a restored loader replays identical link batches."""
  import tempfile
  rng = np.random.default_rng(0)
  n = 60
  rows = rng.integers(0, n, 400)
  cols = rng.integers(0, n, 400)
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='CPU')

  def make_loader():
    return glt.loader.LinkNeighborLoader(
        ds, [2], np.stack([rows, cols]),
        neg_sampling=glt.sampler.NegativeSampling('binary', 1),
        batch_size=16, shuffle=True, seed=3)

  loader = make_loader()
  for _ in loader:
    pass
  with tempfile.TemporaryDirectory() as d:
    mgr = glt.utils.CheckpointManager(d)
    mgr.save(1, {'w': np.zeros(1)}, loader=loader)
    cont = [(np.asarray(b.node), np.asarray(b.metadata['edge_label_index']))
            for b in loader]
    l2 = make_loader()
    mgr.restore({'w': np.zeros(1)}, loader=l2)
    resumed = [(np.asarray(b.node),
                np.asarray(b.metadata['edge_label_index']))
               for b in l2]
    assert len(cont) == len(resumed) > 0
    for (n1, e1), (n2, e2) in zip(cont, resumed):
      np.testing.assert_array_equal(n1, n2)
      np.testing.assert_array_equal(e1, e2)


def test_overflow_policies_local():
  """Calibrated-caps overflow guard on the local loaders: the default
  policy raises at epoch end, 'warn' warns, 'recompute' replays
  offenders at full caps with the same key (byte-identical to the
  uncapped loader), 'off' restores the silent round-3 posture."""
  import pytest
  ds, _ = make_dataset()
  mk = lambda **kw: glt.loader.NeighborLoader(
      ds, [2, 2], np.arange(16), batch_size=4, shuffle=False, seed=0,
      dedup='merge', **kw)

  out = mk(frontier_caps=[8, 8], overflow_policy='off')
  b = next(iter(out))
  assert not bool(np.any(np.asarray(b.metadata['overflow'])))

  with pytest.raises(RuntimeError, match='frontier_caps overflowed'):
    for _ in mk(frontier_caps=[1, 1]):
      pass

  with pytest.warns(UserWarning, match='frontier_caps overflowed'):
    for _ in mk(frontier_caps=[1, 1], overflow_policy='warn'):
      pass

  fix = mk(frontier_caps=[1, 1], overflow_policy='recompute')
  ref = mk(overflow_policy='off')
  steps = 0
  for got, want in zip(fix, ref):
    steps += 1
    np.testing.assert_array_equal(np.asarray(got.node),
                                  np.asarray(want.node))
    np.testing.assert_array_equal(np.asarray(got.edge_index),
                                  np.asarray(want.edge_index))
    np.testing.assert_array_equal(np.asarray(got.edge_mask),
                                  np.asarray(want.edge_mask))
  assert steps == len(ref) > 0
  assert fix.overflow_recomputes == steps

  # silent-off parity: tiny caps iterate without raising
  for _ in mk(frontier_caps=[1, 1], overflow_policy='off'):
    pass


def test_frontier_caps_auto_node_loader():
  """frontier_caps='auto' calibrates in-loader (no hand-computed
  widths) and the resulting epoch passes the default raise-guard."""
  ds, _ = make_dataset()
  loader = glt.loader.NeighborLoader(
      ds, [2, 2], np.arange(16), batch_size=4, shuffle=True, seed=0,
      dedup='merge', frontier_caps='auto')
  caps = loader.sampler.frontier_caps
  assert caps is not None and len(caps) == 2
  steps = sum(1 for _ in loader)   # default policy='raise' stays quiet
  assert steps == len(loader)


def test_frontier_caps_auto_link_loader():
  """Link loaders compute their own effective seed width (src+dst+negs)
  for 'auto' calibration — the round-3 footgun is gone."""
  from graphlearn_tpu.sampler.calibrate import link_seed_width
  ds, _ = make_dataset()
  ns = glt.sampler.NegativeSampling('binary', 1.0)
  assert link_seed_width(4, ns) == 2 * 4 + 2 * 4
  assert link_seed_width(4, None) == 8
  rows = np.arange(16) % 16
  cols = (rows * 3 + 1) % 16
  loader = glt.loader.LinkNeighborLoader(
      ds, [2], np.stack([rows, cols]), neg_sampling=ns, batch_size=4,
      shuffle=False, seed=0, dedup='merge', frontier_caps='auto')
  caps = loader.sampler.frontier_caps
  assert caps is not None and len(caps) == 1
  steps = sum(1 for _ in loader)
  assert steps == len(loader)


def test_frontier_caps_auto_hetero_rejected():
  """frontier_caps='auto' on a hetero dataset fails with the sampler's
  clear homogeneous-only contract, not an AttributeError inside
  estimate_frontier_caps; explicit keys are likewise rejected on hetero
  samplers instead of being silently dropped."""
  import jax
  import pytest
  ds, ub = make_hetero_dataset()
  with pytest.raises(ValueError, match='homogeneous-only'):
    glt.loader.NeighborLoader(ds, [2, 2], ('user', np.arange(4)),
                              batch_size=2, frontier_caps='auto')
  with pytest.raises(ValueError, match='homogeneous-only'):
    glt.loader.LinkNeighborLoader(ds, [2, 2],
                                  (('user', 'buys', 'item'), ub),
                                  batch_size=3, frontier_caps='auto')
  sampler = glt.sampler.NeighborSampler(ds.graph, [2], edge_dir='out')
  with pytest.raises(NotImplementedError, match='homogeneous-only'):
    sampler.sample_from_nodes(
        glt.sampler.NodeSamplerInput(np.arange(2), input_type='user'),
        key=jax.random.PRNGKey(0))


def test_link_loader_overflow_recompute():
  """Too-small caps on the LINK loader: replay at full caps with the
  same key equals the uncapped loader (negatives included)."""
  ds, _ = make_dataset()
  rows = np.arange(16)
  cols = (rows * 5 + 2) % 16
  ns = glt.sampler.NegativeSampling('triplet', 1.0)
  mk = lambda **kw: glt.loader.LinkNeighborLoader(
      ds, [2], np.stack([rows, cols]), neg_sampling=ns, batch_size=4,
      shuffle=False, seed=0, dedup='merge', **kw)
  fix = mk(frontier_caps=[1], overflow_policy='recompute')
  ref = mk(overflow_policy='off')
  steps = 0
  for got, want in zip(fix, ref):
    steps += 1
    np.testing.assert_array_equal(np.asarray(got.node),
                                  np.asarray(want.node))
    np.testing.assert_array_equal(np.asarray(got.edge_index),
                                  np.asarray(want.edge_index))
    md_g, md_w = got.metadata, want.metadata
    np.testing.assert_array_equal(np.asarray(md_g['dst_neg_index']),
                                  np.asarray(md_w['dst_neg_index']))
  assert steps == len(ref) > 0
  assert fix.overflow_recomputes == steps


def test_overflow_guard_edges():
  """Guard edge cases: legacy exact engines reject frontier_caps (no
  overflow contract), and an early-exited epoch's stale flag must not
  taint the next epoch's verdict."""
  import pytest
  ds, _ = make_dataset()
  for mode in ('map_table', 'sort_legacy'):
    with pytest.raises(ValueError, match='legacy'):
      glt.loader.NeighborLoader(ds, [2], np.arange(16), batch_size=4,
                                dedup=mode, frontier_caps=[4])
  # a stale flag left by an early-exited (broken) epoch must be dropped
  # when the next epoch starts — a clean epoch must not raise from it
  import jax.numpy as jnp
  loader = glt.loader.NeighborLoader(
      ds, [2, 2], np.arange(16), batch_size=4, shuffle=False, seed=0,
      dedup='merge', frontier_caps=[16, 16])   # generous: never overflows
  loader._ovf_accum = jnp.asarray(True)        # poison: simulated stale flag
  for _ in loader:                             # full clean epoch
    pass                                       # must not raise
  assert loader._ovf_accum is None


@pytest.mark.slow  # tier-1 budget (PR 18): loader-layer hetero-caps
# policies — the sampler-layer structure/overflow test and the dist
# hetero-caps test stay tier-1 as the family reps
def test_hetero_loader_calibrated_caps_policies():
  """Hetero NeighborLoader under dict-form calibrated caps: quiet epoch
  with calibrated caps under the default raise policy; tiny caps raise
  at epoch end; 'recompute' is rejected (no replayable hetero key)."""
  import pytest
  rng = np.random.default_rng(3)
  n_p, n_a = 300, 150
  cites = np.stack([rng.integers(0, n_p, n_p * 5),
                    rng.integers(0, n_p, n_p * 5)])
  writes = np.stack([rng.integers(0, n_a, n_a * 3),
                     rng.integers(0, n_p, n_a * 3)])
  CITES = ('paper', 'cites', 'paper')
  WRITES = ('author', 'writes', 'paper')
  REV = ('paper', 'rev_writes', 'author')
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph({CITES: cites, WRITES: writes, REV: writes[::-1].copy()},
                graph_mode='CPU',
                num_nodes={CITES: n_p, WRITES: n_a, REV: n_p})
  ds.init_node_features(
      {'paper': rng.standard_normal((n_p, 8)).astype(np.float32),
       'author': rng.standard_normal((n_a, 8)).astype(np.float32)})
  ds.init_node_labels({'paper': rng.integers(0, 4, n_p)})
  fan = [3, 2]
  caps = glt.sampler.estimate_hetero_frontier_caps(
      ds.graph, fan, {'paper': 16}, num_probes=6, slack=1.5, multiple=8)

  loader = glt.loader.NeighborLoader(
      ds, fan, ('paper', np.arange(48)), batch_size=16, shuffle=False,
      seed=0, dedup='merge', frontier_caps=caps)
  steps = 0
  for b in loader:   # default policy='raise' must stay quiet
    steps += 1
    assert 'paper' in b.x and b.x['paper'].shape[1] == 8
  assert steps == 3

  tiny = {et: [1] * len(fan) for et in ds.graph}
  with pytest.raises(RuntimeError, match='frontier_caps overflowed'):
    for _ in glt.loader.NeighborLoader(
        ds, fan, ('paper', np.arange(48)), batch_size=16, shuffle=False,
        seed=0, dedup='merge', frontier_caps=tiny):
      pass

  with pytest.warns(UserWarning, match='frontier_caps overflowed'):
    for _ in glt.loader.NeighborLoader(
        ds, fan, ('paper', np.arange(48)), batch_size=16, shuffle=False,
        seed=0, dedup='merge', frontier_caps=tiny,
        overflow_policy='warn'):
      pass

  with pytest.raises(ValueError, match='homogeneous-only'):
    glt.loader.NeighborLoader(
        ds, fan, ('paper', np.arange(48)), batch_size=16, seed=0,
        dedup='merge', frontier_caps=tiny, overflow_policy='recompute')
