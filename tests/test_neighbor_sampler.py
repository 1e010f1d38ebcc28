"""NeighborSampler tests, mirroring the reference's
test/python/test_neighbor_sampler.py (node/edge seeds x with-edge x
weighted) and test_hetero_neighbor_sampler.py. Like the reference, tests
assert structure (membership, degree caps, relabel consistency), not exact
samples (seeded PRNG differs by design)."""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.ops.neighbor import draw_tile_rows
from graphlearn_tpu.sampler import (EdgeSamplerInput, NegativeSampling,
                                    NodeSamplerInput)


def make_graph(mode='CPU'):
  # 8-node graph: i -> (i+1)%8, i -> (i+2)%8, plus hub edges 0 -> all.
  rows, cols = [], []
  for i in range(8):
    rows += [i, i]
    cols += [(i + 1) % 8, (i + 2) % 8]
  for j in range(1, 8):
    rows.append(0)
    cols.append(j)
  ei = np.stack([np.array(rows), np.array(cols)])
  topo = glt.data.Topology(ei, num_nodes=8)
  return glt.data.Graph(topo, mode), topo, ei


def adjacency_set(ei):
  return {(int(r), int(c)) for r, c in zip(ei[0], ei[1])}


@pytest.mark.parametrize('fused', [True, False])
def test_sample_from_nodes_tree_mode(fused):
  """dedup='tree': computation-tree batches — positional slots, no dedup,
  zero random access in the inducer (PERF.md: 4x device speedup on TPU).
  Edges must still be real graph edges relabeled to valid slots, and seed
  slots are identity positions."""
  graph, topo, ei = make_graph()
  adj = adjacency_set(ei)
  sampler = glt.sampler.NeighborSampler(graph, [2, 2], seed=7,
                                        fused=fused, dedup='tree')
  seeds = np.array([0, 3, 3, 5])   # duplicate seed keeps its own slot
  out = sampler.sample_from_nodes(NodeSamplerInput(seeds))
  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  em = np.asarray(out.edge_mask)
  np.testing.assert_array_equal(node[:4], seeds)
  inv = np.asarray(out.metadata['seed_inverse'])
  np.testing.assert_array_equal(inv[:4], [0, 1, 2, 3])
  assert em.sum() > 0
  for r, c, m in zip(row, col, em):
    if not m:
      continue
    # (seed=col slot, neighbor=row slot) must be a real edge
    assert (int(node[c]), int(node[r])) in adj
  # valid-slot count == emitted edge count + seed count (every sampled
  # edge creates exactly one new slot in tree mode)
  assert int(out.num_nodes) == int(em.sum()) + 4


def test_padded_adjacency_build():
  """Dense [N, W] table: rows hold a shuffled subset of true neighbors,
  deg clamps at W, epos entries point back at matching CSR positions."""
  from graphlearn_tpu import ops
  graph, topo, ei = make_graph()
  indptr = np.asarray(graph.indptr)
  indices = np.asarray(graph.indices)
  tab, deg, epos = ops.build_padded_adjacency(indptr, indices, 4,
                                              edge_pos=True)
  for v in range(8):
    true_nbrs = indices[indptr[v]:indptr[v + 1]].tolist()
    d = min(len(true_nbrs), 4)
    assert deg[v] == d
    row = tab[v][:d]
    assert set(row.tolist()) <= set(true_nbrs)
    for j in range(d):
      assert indices[epos[v, j]] == row[j]
    assert (tab[v][d:] == ops.FILL).all()


def test_padded_sampler_end_to_end():
  """padded_window sampling: every emitted edge is a real graph edge and
  edge ids resolve to the exact sampled (src, dst) pair."""
  rng = np.random.default_rng(0)
  n = 50
  rows = rng.integers(0, n, 600)
  cols = rng.integers(0, n, 600)
  topo = glt.data.Topology(np.stack([rows, cols]), num_nodes=n)
  g = glt.data.Graph(topo, 'CPU')
  sampler = glt.sampler.NeighborSampler(g, [3, 2], seed=0, dedup='tree',
                                        padded_window=8, with_edge=True)
  out = sampler.sample_from_nodes(NodeSamplerInput(np.array([0, 7, 13])))
  node = np.asarray(out.node)
  em = np.asarray(out.edge_mask)
  eids = np.asarray(out.edge)
  assert em.sum() > 0
  for r, c, e, m in zip(np.asarray(out.row), np.asarray(out.col), eids,
                        em):
    if not m:
      continue
    u, v = int(node[c]), int(node[r])
    assert rows[e] == u and cols[e] == v


def test_block_sampling_end_to_end():
  """strategy='block': cluster sampling over aligned CSR blocks — every
  emitted edge is real, edge ids resolve exactly, and marginals over
  repeated draws are uniform in the mean."""
  rng = np.random.default_rng(0)
  n = 60
  rows = rng.integers(0, n, 900)
  cols = rng.integers(0, n, 900)
  topo = glt.data.Topology(np.stack([rows, cols]), num_nodes=n)
  g = glt.data.Graph(topo, 'CPU')
  indptr = np.asarray(topo.indptr)
  indices = np.asarray(topo.indices)
  adj = {v: set(indices[indptr[v]:indptr[v + 1]].tolist())
         for v in range(n)}
  sampler = glt.sampler.NeighborSampler(g, [5, 3], seed=0, dedup='tree',
                                        strategy='block', with_edge=True)
  out = sampler.sample_from_nodes(NodeSamplerInput(np.arange(16)))
  node = np.asarray(out.node)
  em = np.asarray(out.edge_mask)
  assert em.sum() > 0
  for r, c, e, m in zip(np.asarray(out.row), np.asarray(out.col),
                        np.asarray(out.edge), em):
    if not m:
      continue
    u, v = int(node[c]), int(node[r])
    assert v in adj[u]
    assert rows[e] == u and cols[e] == v
  # fanout > BLOCK rejected up front; so is mixing the two backends
  with pytest.raises(ValueError, match='caps fanouts'):
    glt.sampler.NeighborSampler(g, [32], strategy='block')
  with pytest.raises(ValueError, match='mutually exclusive'):
    glt.sampler.NeighborSampler(g, [5], strategy='block',
                                padded_window=16)
  # marginal uniformity: node 0's neighbors drawn ~1/deg each over many
  # fresh batches (exact in the mean; cluster correlation widens the
  # per-neighbor spread, so the bound is loose)
  from collections import Counter
  s1 = glt.sampler.NeighborSampler(g, [8], seed=1, dedup='tree',
                                   strategy='block')
  cnt = Counter()
  for _ in range(150):
    o = s1.sample_from_nodes(NodeSamplerInput(np.zeros(8, np.int64)))
    nd = np.asarray(o.node)
    for r, m in zip(np.asarray(o.row), np.asarray(o.edge_mask)):
      if m:
        cnt[int(nd[r])] += 1
  deg0 = len(adj[0])
  total = sum(cnt.values())
  freqs = np.array([cnt.get(v, 0) / total for v in sorted(adj[0])])
  assert set(cnt) <= adj[0]
  np.testing.assert_allclose(freqs.sum(), 1.0)
  assert freqs.min() > 0.2 / deg0 and freqs.max() < 3.0 / deg0


def test_hetero_block_sampling():
  """strategy='block' in the typed engine: per-etype block tables, edges
  valid per etype."""
  et = ('u', 'to', 'v')
  rev = glt.typing.reverse_edge_type(et)
  n = 40
  ei = np.stack([np.arange(n), (np.arange(n) + 1) % n])
  graphs = {et: glt.data.Graph(glt.data.Topology(ei, num_nodes=n), 'CPU')}
  sampler = glt.sampler.NeighborSampler(graphs, {et: [2]}, seed=0,
                                        dedup='tree', strategy='block')
  out = sampler.sample_from_nodes(NodeSamplerInput(np.array([0, 7]), 'u'))
  nu = np.asarray(out.node['u'])
  nv = np.asarray(out.node['v'])
  m = np.asarray(out.edge_mask[rev])
  assert m.sum() > 0
  for ri, ci in zip(np.asarray(out.row[rev])[m],
                    np.asarray(out.col[rev])[m]):
    assert int(nv[ri]) == (int(nu[ci]) + 1) % n


def test_hetero_tree_mode():
  """Typed tree mode: per-type positional slots, edges valid per etype."""
  et = ('u', 'to', 'v')
  rev = glt.typing.reverse_edge_type(et)
  ei = np.stack([np.arange(8), (np.arange(8) + 1) % 8])
  topo = glt.data.Topology(ei, num_nodes=8)
  graphs = {et: glt.data.Graph(topo, 'CPU')}
  sampler = glt.sampler.NeighborSampler(graphs, {et: [2]}, seed=0,
                                        dedup='tree')
  out = sampler.sample_from_nodes(NodeSamplerInput(np.array([0, 3]), 'u'))
  nu = np.asarray(out.node['u'])
  nv = np.asarray(out.node['v'])
  np.testing.assert_array_equal(nu[:2], [0, 3])
  r = np.asarray(out.row[rev])
  c = np.asarray(out.col[rev])
  m = np.asarray(out.edge_mask[rev])
  assert m.sum() > 0
  for ri, ci in zip(r[m], c[m]):
    u, v = int(nu[ci]), int(nv[ri])
    assert v == (u + 1) % 8


def test_tree_mode_trains_equivalently():
  """A jitted SAGE step consumes tree-mode batches unchanged (padded
  shapes; seed slots lead)."""
  import jax
  graph, topo, ei = make_graph()
  ds = glt.data.Dataset()
  ds.init_graph(ei, num_nodes=8, graph_mode='CPU')
  ds.init_node_features(np.eye(8, dtype=np.float32))
  ds.init_node_labels(np.arange(8) % 2)
  from graphlearn_tpu.models import GraphSAGE, train as train_lib
  loader = glt.loader.NeighborLoader(ds, [2, 2], np.arange(8),
                                     batch_size=4, seed=0, dedup='tree')
  model = GraphSAGE(hidden_dim=8, out_dim=2, num_layers=2)
  first = train_lib.batch_to_dict(next(iter(loader)))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  train_step, _ = train_lib.make_train_step(model, tx, 2)
  for batch in loader:
    state, loss, acc = train_step(state, train_lib.batch_to_dict(batch))
  assert np.isfinite(float(loss))


@pytest.mark.parametrize('with_edge', [False, True])
def test_sample_from_nodes_homo(with_edge):
  graph, topo, ei = make_graph()
  adj = adjacency_set(ei)
  sampler = glt.sampler.NeighborSampler(graph, [2, 2], with_edge=with_edge,
                                        seed=42)
  seeds = np.array([0, 3, 3, 5])
  out = sampler.sample_from_nodes(NodeSamplerInput(seeds)).trim()

  # Seeds come first and deduped.
  assert set(out.node[:3].tolist()) == {0, 3, 5}
  assert len(set(out.node.tolist())) == out.num_nodes
  # All emitted edges are real edges, in message direction (row -> col means
  # col sampled row as neighbor, so (col, row) must be a graph edge).
  for r, c in zip(out.row, out.col):
    u, v = int(out.node[c]), int(out.node[r])
    assert (u, v) in adj
  if with_edge:
    assert out.edge.shape == out.row.shape
    # edge ids are original COO input positions (Topology default); each
    # sampled edge id must decode to (seed, neighbor) of its row/col pair.
    for e, r, c in zip(out.edge, out.row, out.col):
      assert ei[0][e] == int(out.node[c])
      assert ei[1][e] == int(out.node[r])


def test_fanout_cap():
  graph, _, _ = make_graph()
  sampler = glt.sampler.NeighborSampler(graph, [3], seed=0)
  out = sampler.sample_from_nodes(NodeSamplerInput(np.array([0])))
  # node 0 has degree 9 but fanout 3: exactly 3 edges sampled.
  assert int(np.asarray(out.num_sampled_edges[0])) == 3


def test_weighted_sampling_bias():
  # node 0 -> {1..5}; weight on edge (0,1) dominates.
  rows = np.zeros(5, np.int64)
  cols = np.arange(1, 6)
  w = np.array([100.0, 1e-6, 1e-6, 1e-6, 1e-6], np.float32)
  topo = glt.data.Topology(np.stack([rows, cols]), edge_weights=w,
                           num_nodes=6)
  graph = glt.data.Graph(topo, 'CPU')
  sampler = glt.sampler.NeighborSampler(graph, [3], with_weight=True,
                                        seed=1)
  out = sampler.sample_from_nodes(NodeSamplerInput(np.array([0]))).trim()
  # With deg=5 > k=3, draws are weight-biased: node 1 must appear.
  sampled_globals = {int(out.node[r]) for r in out.row}
  assert 1 in sampled_globals


def test_sample_from_edges_binary():
  graph, _, ei = make_graph()
  adj = adjacency_set(ei)
  sampler = glt.sampler.NeighborSampler(graph, [2], seed=3)
  inputs = EdgeSamplerInput(
      row=ei[0][:4].copy(), col=ei[1][:4].copy(),
      neg_sampling=NegativeSampling('binary', 1))
  out = sampler.sample_from_edges(inputs)
  eli = np.asarray(out.metadata['edge_label_index'])
  label = np.asarray(out.metadata['edge_label'])
  assert eli.shape == (2, 8)
  assert label[:4].sum() == 4 and label[4:].sum() == 0
  node = np.asarray(out.node)
  # positive pairs decode back to the seed edges
  for j in range(4):
    u, v = int(node[eli[0, j]]), int(node[eli[1, j]])
    assert (u, v) in adj


def test_sample_from_edges_triplet():
  graph, _, ei = make_graph()
  sampler = glt.sampler.NeighborSampler(graph, [2], seed=4)
  inputs = EdgeSamplerInput(
      row=ei[0][:3].copy(), col=ei[1][:3].copy(),
      neg_sampling=NegativeSampling('triplet', 2))
  out = sampler.sample_from_edges(inputs)
  assert np.asarray(out.metadata['src_index']).shape == (3,)
  assert np.asarray(out.metadata['dst_pos_index']).shape == (3,)
  assert np.asarray(out.metadata['dst_neg_index']).shape == (6,)
  node = np.asarray(out.node)
  src = node[np.asarray(out.metadata['src_index'])]
  np.testing.assert_array_equal(src, ei[0][:3])


def test_subgraph():
  graph, _, ei = make_graph()
  adj = adjacency_set(ei)
  sampler = glt.sampler.NeighborSampler(graph, [2], seed=5)
  out = sampler.subgraph(NodeSamplerInput(np.array([0, 1]))).trim()
  node = out.node
  # every edge among collected nodes, relabeled correctly
  for r, c in zip(out.row, out.col):
    assert (int(node[r]), int(node[c])) in adj
  # mapping points each seed at its slot in node
  mapping = np.asarray(out.metadata['mapping'])
  assert node[mapping[0]] == 0 and node[mapping[1]] == 1


def test_sample_prob():
  graph, _, _ = make_graph()
  sampler = glt.sampler.NeighborSampler(graph, [2, 2], seed=6)
  prob = np.asarray(sampler.sample_prob(np.array([0]), 8))
  assert prob[0] == 1.0
  assert (prob >= 0).all() and (prob <= 1).all()
  # direct neighbors of 0 have positive probability
  assert prob[1] > 0 and prob[2] > 0


def make_hetero():
  # user(3) -- buys --> item(4); item -- rev_buys --> user
  ub = np.array([[0, 0, 1, 2, 2], [0, 1, 2, 3, 0]])
  bu = ub[::-1].copy()
  graphs = {}
  t1 = glt.data.Topology(ub, num_nodes=3)
  t2 = glt.data.Topology(bu, num_nodes=4)
  graphs[('user', 'buys', 'item')] = glt.data.Graph(t1, 'CPU')
  graphs[('item', 'rev_buys', 'user')] = glt.data.Graph(t2, 'CPU')
  return graphs, ub


def test_hetero_sample_from_nodes():
  graphs, ub = make_hetero()
  adj = {(int(r), int(c)) for r, c in zip(ub[0], ub[1])}
  sampler = glt.sampler.NeighborSampler(graphs, [2, 2], seed=7)
  out = sampler.sample_from_nodes(
      NodeSamplerInput(np.array([0, 1]), input_type='user')).trim()
  assert 'user' in out.node and 'item' in out.node
  assert set(np.asarray(out.node['user'][:2]).tolist()) == {0, 1}
  # 'out' edge_dir: output keys are reversed etypes, row=neighbor col=seed
  rev = ('item', 'rev_buys', 'user')
  assert rev in out.row
  for r, c in zip(out.row[rev], out.col[rev]):
    item = int(out.node['item'][r])
    user = int(out.node['user'][c])
    assert (user, item) in adj


def test_padded_window_auto_and_stats():
  """'auto' picks the fastest sufficient window while dodging the W=32
  cliff; padded_table_stats quantifies the truncation recall; the
  loader reseeds the table each epoch so truncated hubs expose a fresh
  subset."""
  import graphlearn_tpu as glt
  from graphlearn_tpu import ops
  assert ops.choose_padded_window([15, 10, 5]) == 16
  assert ops.choose_padded_window([20, 10]) == 64    # not 32
  assert ops.choose_padded_window([100]) == 128
  rng = np.random.default_rng(0)
  n = 200
  # hub node 0 with degree 80, everyone else degree <= 4
  rows = np.concatenate([np.zeros(80, np.int64),
                         rng.integers(1, n, 400)])
  cols = rng.integers(0, n, rows.shape[0])
  g = glt.data.Graph(glt.data.Topology(np.stack([rows, cols]),
                                       num_nodes=n), 'CPU')
  stats = ops.padded_table_stats(g.topo.indptr, 16)
  assert stats['frac_truncated_nodes'] > 0
  assert 0 < stats['edge_recall'] < 1
  assert stats['node_recall'] > stats['edge_recall']  # hubs drag edges

  # per-epoch reseed: the hub's sampled neighbor SET changes across
  # epochs (same loader, fresh table), and stays fixed within an epoch
  ds = glt.data.Dataset(graph=g)
  ds.init_node_features(rng.standard_normal((n, 4), dtype=np.float32))
  loader = glt.loader.NeighborLoader(
      ds, [8], np.zeros(8, np.int64), batch_size=8, seed=0,
      dedup='tree', padded_window='auto')
  assert loader.sampler.padded_window == 16
  # compare the TABLE itself across epochs (a draw-level check could
  # pass via per-call PRNG folding even with the reseed broken)
  for _ in loader:
    pass
  hub_row1 = np.asarray(
      loader.sampler._padded_arrays()['tab'])[0].copy()
  for _ in loader:   # epoch 2 start triggers the reseed
    pass
  hub_row2 = np.asarray(loader.sampler._padded_arrays()['tab'])[0]
  # hub degree 80 >> window 16: two independent 16-subsets differ w.h.p.
  assert set(hub_row1.tolist()) != set(hub_row2.tolist())


@pytest.mark.parametrize('strategy,padded,dedup', [
    # tier-1 keeps every dedup mode on the base (random, unpadded)
    # engine plus exact ('map') + tree representatives per alternate
    # backend; the remaining backend x dedup cross-terms are `slow`
    # (the dedup engines are backend-independent — tier-1 wall-budget
    # canary; the full grid runs under -m slow)
    ('random', None, 'map'), ('random', None, 'map_capped'),
    ('random', None, 'map_table'),
    # exact dedup under CALIBRATED caps wide enough that
    # ops.uniform_sample draws hop 1 tile by tile (PR 27), fused program
    # against the per-op chain
    ('random', None, 'map_tiled'),
    ('random', None, 'tree'),
    ('block', None, 'tree'),
    ('random', 8, 'tree'),
    # tier-1 wall budget (PR 16): padded x map duplicates coverage of
    # random x map (engine) + random-8 x tree (padding) — slow keeps it
    pytest.param('random', 8, 'map', marks=pytest.mark.slow),
    # tier-1 wall budget (PR 8): sort_legacy is the LEGACY dedup path
    # and block x map duplicates coverage carried by block x tree +
    # random x map — both keep running under -m slow
    pytest.param('random', None, 'sort_legacy', marks=pytest.mark.slow),
    pytest.param('block', None, 'map', marks=pytest.mark.slow),
    pytest.param('block', None, 'map_capped', marks=pytest.mark.slow),
    pytest.param('block', None, 'map_table', marks=pytest.mark.slow),
    pytest.param('block', None, 'sort_legacy', marks=pytest.mark.slow),
    pytest.param('random', 8, 'map_capped', marks=pytest.mark.slow),
    pytest.param('random', 8, 'map_table', marks=pytest.mark.slow),
    pytest.param('random', 8, 'sort_legacy', marks=pytest.mark.slow),
])
def test_sampler_invariants_random_graphs(dedup, strategy, padded):
  """Property sweep over the mode matrix on random graphs: every valid
  emitted edge decodes to a REAL graph edge, seed slots lead, exact
  modes produce a duplicate-free compact node buffer, and masked slots
  never leak ids."""
  import zlib
  rng = np.random.default_rng(
      zlib.adler32(f'{dedup}-{strategy}-{padded}'.encode()))
  # fixed fanouts/batch so every mode shares ONE compiled program
  # (_fused_homo_fn is module-cached on the static signature); the
  # randomness lives in the graphs and seeds
  tiled = dedup == 'map_tiled'
  fanouts, b = ([10, 2], 256) if tiled else ([3, 2], 8)
  assert padded is None or padded >= max(fanouts)
  for trial in range(1 if tiled else 3):
    n = 6000 if tiled else int(rng.integers(30, 200))
    e = 12 * n if tiled else int(rng.integers(2 * n, 8 * n))
    rows = rng.integers(0, n, e)
    cols = rng.integers(0, n, e)
    adj = {(int(r), int(c)) for r, c in zip(rows, cols)}
    graph = glt.data.Graph(
        glt.data.Topology(np.stack([rows, cols]), num_nodes=n), 'CPU')
    # 'map_capped' = exact dedup under DELIBERATELY tight frontier caps:
    # truncation may trip (clean by contract), every invariant below
    # must still hold
    caps = [16, 24] if dedup == 'map_capped' else None
    if tiled:
      caps = glt.sampler.estimate_frontier_caps(graph, fanouts, b,
                                                num_probes=3)
      assert draw_tile_rows(caps[0]) and caps[0] < b * fanouts[0]
    kw = dict(seed=trial, strategy=strategy, padded_window=padded,
              frontier_caps=caps,
              dedup='map' if dedup in ('map_capped', 'map_tiled') else dedup)
    s = glt.sampler.NeighborSampler(graph, fanouts, fused=True, **kw)
    seeds = rng.integers(0, n, b)
    out = s.sample_from_nodes(NodeSamplerInput(seeds), batch_cap=b)
    if tiled:
      # the per-op chain draws the same stream through the same tiles
      per_op = glt.sampler.NeighborSampler(
          graph, fanouts, fused=False, **kw).sample_from_nodes(
              NodeSamplerInput(seeds), batch_cap=b)
      for field in ('node', 'row', 'col', 'edge_mask'):
        np.testing.assert_array_equal(np.asarray(getattr(out, field)),
                                      np.asarray(getattr(per_op, field)))
    node = np.asarray(out.node)
    r = np.asarray(out.row)
    c = np.asarray(out.col)
    em = np.asarray(out.edge_mask)
    nn = int(out.num_nodes)
    # seeds lead (dedup modes compact; tree keeps positional seeds)
    if dedup != 'tree':
      uniq_seeds = len(set(seeds.tolist()))
      assert set(node[:uniq_seeds]) <= set(seeds.tolist())
      valid = node[:nn]
      assert len(set(valid.tolist())) == nn        # no dupes
      assert (node[nn:] == -1).all()               # compact
    else:
      np.testing.assert_array_equal(node[:b], seeds)
    for j in np.where(em)[0]:
      assert node[r[j]] >= 0 and node[c[j]] >= 0
      # padded mode samples from the table's W-subset of real neighbors;
      # all modes must emit only real edges
      assert (int(node[c[j]]), int(node[r[j]])) in adj
    # masked edge slots must not carry live local indices
    dead = ~em
    assert ((r[dead] == -1) | (c[dead] == -1)).all() or not dead.any()


# ---------------- calibrated hetero caps (per-(hop, etype)) ----------------

def make_hetero_medium(n_paper=400, n_author=200, seed=0):
  """IGBH-shaped typed graph: cites + writes + rev_writes."""
  rng = np.random.default_rng(seed)
  cites = np.stack([rng.integers(0, n_paper, n_paper * 6),
                    rng.integers(0, n_paper, n_paper * 6)])
  writes = np.stack([rng.integers(0, n_author, n_author * 4),
                     rng.integers(0, n_paper, n_author * 4)])
  rev = writes[::-1].copy()
  mk = lambda ei, n: glt.data.Graph(
      glt.data.Topology(ei, num_nodes=n), 'CPU')
  return {('paper', 'cites', 'paper'): mk(cites, n_paper),
          ('author', 'writes', 'paper'): mk(writes, n_author),
          ('paper', 'rev_writes', 'author'): mk(rev, n_paper)}


def _hetero_adj(graphs):
  adj = {}
  for et, g in graphs.items():
    r, c = g.topo.to_coo()
    adj[et] = {(int(a), int(b)) for a, b in zip(r, c)}
  return adj


def test_estimate_hetero_frontier_caps_shrinks_plan():
  """Calibrated per-(hop, etype) caps come in far below the compounding
  worst case (the reason a reference-shaped 3-hop hetero config is
  statically infeasible without them)."""
  from graphlearn_tpu.sampler.neighbor_sampler import hetero_capacity_plan
  graphs = make_hetero_medium()
  fan = [3, 2]
  caps = glt.sampler.estimate_hetero_frontier_caps(
      graphs, fan, {'paper': 64}, num_probes=4, slack=1.5, multiple=8)
  assert set(caps) == {tuple(et) for et in graphs}
  assert all(len(v) == len(fan) for v in caps.values())
  fo = lambda et: fan
  ets = list(graphs)
  _, _, full = hetero_capacity_plan(ets, fo, {'paper': 64}, 'out')
  _, _, cal = hetero_capacity_plan(ets, fo, {'paper': 64}, 'out',
                                   etype_caps=caps)
  # every type's buffer shrinks; the deepest compounding type shrinks a lot
  assert all(cal[t] <= full[t] for t in full)
  assert sum(cal.values()) < 0.7 * sum(full.values())


@pytest.mark.slow  # tier-1 budget (PR 18): worst-case-caps variant of
# test_hetero_calibrated_caps_structure_and_overflow, which stays
def test_hetero_caps_at_worst_case_are_byte_identical():
  """Caps set exactly to the worst-case widths make the clamped engine a
  structural no-op: byte-identical output to the uncapped sampler (same
  shapes, same PRNG stream) — validates the max_new threading."""
  from graphlearn_tpu.sampler.neighbor_sampler import hetero_capacity_plan
  graphs = make_hetero_medium()
  fan = [3, 2]
  b = 32
  ets = list(graphs)
  _, hop_caps, _ = hetero_capacity_plan(ets, lambda et: fan,
                                        {'paper': b}, 'out')
  worst = {}
  for h, per_et in enumerate(hop_caps):
    for et, (fcap, k, cap) in per_et.items():
      assert cap == fcap * k
      worst.setdefault(et, [0] * len(hop_caps))[h] = cap
  base = glt.sampler.NeighborSampler(graphs, fan, seed=3, dedup='merge')
  capped = glt.sampler.NeighborSampler(graphs, fan, seed=3, dedup='merge',
                                       frontier_caps=worst)
  seeds = np.arange(b)
  inp = NodeSamplerInput(seeds, input_type='paper')
  o1 = base.sample_from_nodes(inp)
  o2 = capped.sample_from_nodes(inp)
  assert not bool(np.asarray(o2.metadata['overflow']))
  for t in o1.node:
    np.testing.assert_array_equal(np.asarray(o1.node[t]),
                                  np.asarray(o2.node[t]))
  for et in o1.row:
    np.testing.assert_array_equal(np.asarray(o1.row[et]),
                                  np.asarray(o2.row[et]))
    np.testing.assert_array_equal(np.asarray(o1.edge_mask[et]),
                                  np.asarray(o2.edge_mask[et]))


def test_hetero_calibrated_caps_structure_and_overflow():
  """Under real calibrated caps: buffers shrink, no overflow at the
  calibrated batch shape, valid edges decode to real typed edges, node
  buffers dedup; tiny caps trip the on-device overflow flag."""
  graphs = make_hetero_medium()
  adj = _hetero_adj(graphs)
  fan = [3, 2]
  b = 32
  caps = glt.sampler.estimate_hetero_frontier_caps(
      graphs, fan, {'paper': b}, num_probes=6, slack=1.5, multiple=8)
  s = glt.sampler.NeighborSampler(graphs, fan, seed=5, dedup='merge',
                                  frontier_caps=caps)
  rng = np.random.default_rng(1)
  for _ in range(3):
    seeds = rng.integers(0, 400, b)
    out = s.sample_from_nodes(NodeSamplerInput(seeds, input_type='paper'))
    assert not bool(np.asarray(out.metadata['overflow']))
    for t, buf in out.node.items():
      nn = int(out.num_nodes[t])
      valid = np.asarray(buf[:nn])
      assert len(set(valid.tolist())) == nn           # exact dedup
    for et in out.row:
      r = np.asarray(out.row[et])
      c = np.asarray(out.col[et])
      em = np.asarray(out.edge_mask[et])
      src_t, dst_t = et[0], et[2]
      stored = (dst_t, et[1].replace('rev_', ''), src_t) \
          if et[1].startswith('rev_') else et
      for j in np.flatnonzero(em)[:50]:
        u = int(np.asarray(out.node[src_t])[r[j]])
        v = int(np.asarray(out.node[dst_t])[c[j]])
        # emitted under message-flow orientation of a stored etype
        ok = (u, v) in adj.get(et, set()) or \
            (v, u) in adj.get(stored, set())
        assert ok, (et, u, v)

  tiny = {et: [1] * len(fan) for et in graphs}
  s_tiny = glt.sampler.NeighborSampler(graphs, fan, seed=5, dedup='merge',
                                       frontier_caps=tiny)
  out = s_tiny.sample_from_nodes(
      NodeSamplerInput(np.arange(b), input_type='paper'))
  assert bool(np.asarray(out.metadata['overflow']))


def test_hetero_caps_validation():
  graphs = make_hetero_medium()
  homo_g, _, _ = make_graph()
  with pytest.raises(ValueError, match='homogeneous-only'):
    glt.sampler.NeighborSampler(graphs, [2], dedup='merge',
                                frontier_caps=[4])
  with pytest.raises(ValueError, match='hetero-only'):
    glt.sampler.NeighborSampler(homo_g, [2], dedup='merge',
                                frontier_caps={('a', 'b', 'c'): [4]})
  with pytest.raises(ValueError, match='not in'):
    glt.sampler.NeighborSampler(graphs, [2], dedup='merge',
                                frontier_caps={('x', 'y', 'z'): [4]})
  with pytest.raises(ValueError, match='exact-dedup'):
    glt.sampler.NeighborSampler(
        graphs, [2], dedup='tree',
        frontier_caps={('paper', 'cites', 'paper'): [4]})


@pytest.mark.slow  # tier-1 wall budget (PR 8): the structure/overflow
def test_hetero_caps_invariants_random_graphs():   # + worst-case-bytes
  """(hetero-caps family reps stay tier-1.) Property sweep of the CLAMPED typed engine over random typed
  graphs x random per-(hop, etype) caps: every valid emitted edge
  decodes to a real typed edge, per-type node buffers stay
  duplicate-free and compact, counts respect the clamped plan, the
  overflow flag fires IFF some (hop, etype) truncated (checked against
  the plan's caps), and seed slots lead the input type's buffer."""
  import zlib
  from graphlearn_tpu.sampler.neighbor_sampler import hetero_capacity_plan
  rng = np.random.default_rng(zlib.adler32(b'hetero-caps-sweep'))
  fan = [3, 2]
  b = 8
  for trial in range(4):
    n_u = int(rng.integers(30, 120))
    n_v = int(rng.integers(20, 80))
    e1 = int(rng.integers(2 * n_u, 6 * n_u))
    e2 = int(rng.integers(2 * n_v, 6 * n_v))
    UV, VU = ('u', 'to', 'v'), ('v', 'back', 'u')
    ei1 = np.stack([rng.integers(0, n_u, e1), rng.integers(0, n_v, e1)])
    ei2 = np.stack([rng.integers(0, n_v, e2), rng.integers(0, n_u, e2)])
    graphs = {
        UV: glt.data.Graph(glt.data.Topology(ei1, num_nodes=n_u), 'CPU'),
        VU: glt.data.Graph(glt.data.Topology(ei2, num_nodes=n_v), 'CPU')}
    adj = {UV: {(int(r), int(c)) for r, c in zip(ei1[0], ei1[1])},
           VU: {(int(r), int(c)) for r, c in zip(ei2[0], ei2[1])}}
    # random caps: sometimes generous, sometimes deliberately tight
    caps = {et: [int(rng.integers(1, 3) * 4 * (h + 1))
                 for h in range(len(fan))] for et in graphs}
    s = glt.sampler.NeighborSampler(graphs, fan, seed=trial,
                                    dedup='merge', frontier_caps=caps)
    seeds = rng.integers(0, n_u, b)
    out = s.sample_from_nodes(NodeSamplerInput(seeds, input_type='u'),
                              batch_cap=b)
    # plan-level counts: per-type totals stay within the clamped plan
    _, _, node_caps = hetero_capacity_plan(
        list(graphs), lambda et: fan, {'u': b}, 'out', etype_caps=caps)
    for t, buf in out.node.items():
      nn = int(out.num_nodes[t])
      assert nn <= node_caps[t]
      valid = np.asarray(buf[:nn])
      assert len(set(valid.tolist())) == nn       # exact dedup
      assert (np.asarray(buf[nn:]) == -1).all()   # compact
    # seeds lead the input type's buffer
    uniq_seeds = set(seeds.tolist())
    assert set(np.asarray(out.node['u'][:len(uniq_seeds)]).tolist()) \
        == uniq_seeds
    # every valid emitted edge decodes to a real typed edge (emitted
    # under message-flow orientation = reversed stored etype)
    for out_et in out.row:
      stored = glt.typing.reverse_edge_type(out_et)
      r = np.asarray(out.row[out_et])
      c = np.asarray(out.col[out_et])
      m = np.asarray(out.edge_mask[out_et])
      src_buf = np.asarray(out.node[out_et[0]])
      dst_buf = np.asarray(out.node[out_et[2]])
      for j in np.flatnonzero(m):
        child = int(src_buf[r[j]])
        parent = int(dst_buf[c[j]])
        assert (parent, child) in adj[stored], (out_et, parent, child)
      dead = ~m
      assert ((r[dead] == -1) | (c[dead] == -1)).all() or not dead.any()
    # overflow flag is accurate: re-run UNCAPPED with the same seed and
    # compare per-(hop, etype) new-unique counts against the caps
    s_full = glt.sampler.NeighborSampler(graphs, fan, seed=trial,
                                         dedup='merge')
    out_full = s_full.sample_from_nodes(
        NodeSamplerInput(seeds, input_type='u'), batch_cap=b)
    flagged = bool(np.asarray(out.metadata['overflow']))
    if not flagged:
      # no truncation claimed -> the capped run kept every node the
      # uncapped run found (same PRNG stream, same draws)
      for t in out_full.node:
        full_set = set(np.asarray(
            out_full.node[t][:int(out_full.num_nodes[t])]).tolist())
        cap_set = set(np.asarray(
            out.node[t][:int(out.num_nodes[t])]).tolist())
        assert full_set == cap_set, (trial, t)
