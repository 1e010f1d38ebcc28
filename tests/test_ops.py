"""Kernel-level golden tests on tiny hand-built CSRs.

Mirrors the reference C++ kernel tests (test/cpp/test_random_sampler.cu,
test_inducer.cu, test_subgraph.cu, test_random_negative_sampler.cu,
test_hash_table.cu): structure assertions (degree caps, membership, reindex
consistency), not exact samples, since sampling is seeded-random.
"""
import jax
import pytest
import jax.numpy as jnp
import numpy as np

from graphlearn_tpu import ops
from graphlearn_tpu.data import Topology


def chain_star_topo():
  """4-node graph: 0->{1,2,3}, 1->{2}, 2->{3}, 3->{}."""
  row = np.array([0, 0, 0, 1, 2])
  col = np.array([1, 2, 3, 2, 3])
  return Topology(np.stack([row, col]), num_nodes=4)


def dev(topo):
  return jnp.asarray(topo.indptr.astype(np.int32)), jnp.asarray(topo.indices)


# ---------------------------------------------------------------- unique

def test_masked_unique():
  ids = jnp.array([5, 3, 5, 7, 3, 9], dtype=jnp.int32)
  mask = jnp.array([True, True, True, True, True, False])
  uniq, count, inv = ops.masked_unique(ids, mask, size=6)
  assert int(count) == 3
  assert uniq[:3].tolist() == [3, 5, 7]
  assert uniq[3:].tolist() == [ops.FILL] * 3
  # inverse maps each valid position to its unique slot
  np.testing.assert_array_equal(np.asarray(uniq)[np.asarray(inv[:5])],
                                np.asarray(ids[:5]))
  assert int(inv[5]) == -1


def test_masked_unique_all_masked():
  ids = jnp.array([1, 2], dtype=jnp.int32)
  uniq, count, inv = ops.masked_unique(ids, jnp.zeros(2, bool), size=2)
  assert int(count) == 0
  assert uniq.tolist() == [ops.FILL, ops.FILL]
  assert inv.tolist() == [-1, -1]


# ---------------------------------------------------------------- sampling

def test_uniform_sample_structure():
  topo = chain_star_topo()
  indptr, indices = dev(topo)
  seeds = jnp.array([0, 3, 2], dtype=jnp.int32)
  mask = jnp.ones(3, bool)
  nbrs, epos, m = ops.uniform_sample(indptr, indices, seeds, mask, 2,
                                     jax.random.PRNGKey(0))
  assert nbrs.shape == (3, 2)
  # seed 0 has deg 3 > k=2: both valid, members of {1,2,3}
  assert bool(m[0].all())
  assert set(np.asarray(nbrs[0]).tolist()) <= {1, 2, 3}
  # seed 3 has deg 0: nothing valid
  assert not bool(m[1].any())
  assert nbrs[1].tolist() == [ops.FILL] * 2
  # seed 2 has deg 1 <= k: exactly neighbor 3, in order
  assert m[2].tolist() == [True, False]
  assert int(nbrs[2, 0]) == 3
  # epos points at real CSR slots
  assert int(indices[epos[2, 0]]) == 3


def test_uniform_sample_deg_le_k_keeps_all():
  topo = chain_star_topo()
  indptr, indices = dev(topo)
  seeds = jnp.array([0], dtype=jnp.int32)
  nbrs, _, m = ops.uniform_sample(indptr, indices, seeds, jnp.ones(1, bool),
                                  5, jax.random.PRNGKey(1))
  assert m[0].tolist() == [True, True, True, False, False]
  assert set(np.asarray(nbrs[0, :3]).tolist()) == {1, 2, 3}


def test_uniform_sample_masked_seed():
  topo = chain_star_topo()
  indptr, indices = dev(topo)
  seeds = jnp.array([0, 0], dtype=jnp.int32)
  mask = jnp.array([True, False])
  _, _, m = ops.uniform_sample(indptr, indices, seeds, mask, 2,
                               jax.random.PRNGKey(2))
  assert not bool(m[1].any())


def _untiled_uniform_sample(indptr, indices, seeds, seed_mask, k, key,
                            meta=None):
  """ops.uniform_sample as it read before it was tiled: one gather over
  the whole cap. The tiled op must equal this in every element."""
  b = seeds.shape[0]
  safe_seeds = jnp.where(seed_mask, seeds, 0)
  if meta is not None:
    row = meta[safe_seeds]
    start, deg = row[:, 0], row[:, 1]
  else:
    start = indptr[safe_seeds]
    deg = indptr[safe_seeds + 1] - start
  u = jax.random.uniform(key, (b, k))
  rand_off = jnp.floor(u * deg[:, None].astype(u.dtype)).astype(jnp.int32)
  rand_off = jnp.minimum(rand_off, jnp.maximum(deg[:, None] - 1, 0))
  seq_off = jnp.arange(k, dtype=jnp.int32)[None, :]
  offsets = jnp.where(deg[:, None] > k, rand_off, seq_off)
  mask = seed_mask[:, None] & (offsets < deg[:, None])
  epos = start[:, None] + offsets
  safe_epos = jnp.where(mask, epos, 0)
  nbrs = jnp.where(mask, indices[safe_epos], ops.FILL)
  return nbrs, jnp.where(mask, epos, 0), mask


def _random_csr(rng, n, max_deg):
  """Degrees 0..max_deg (so both the keep-all and the random branch of a
  fan-out below max_deg run), row 0 non-empty: masked rows gather it."""
  deg = rng.integers(0, max_deg + 1, n)
  deg[0] = max_deg
  indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
  indices = rng.integers(0, n, indptr[-1]).astype(np.int32)
  return indptr, indices


@pytest.mark.parametrize('b,mask_kind,k,use_meta', [
    (2560, 'none', 5, True),            # 0 % valid: no tile runs
    (2560, 'one_row', 10, True),
    (2560, 'prefix_66', 15, True),
    (2560, 'prefix_66', 5, False),      # the two indptr gathers
    (2560, 'full', 10, False),
    (2560, 'scattered', 5, True),       # last valid row near the cap
    (3333, 'prefix_66', 10, True),      # b not a multiple of the tile
    (3333, 'full', 15, False),          # ... and the clamped last tile runs
    (3333, 'scattered', 10, False),
    (20000, 'prefix_66', 5, True),      # a cap past 64 x 256: 512-row tiles
    (1500, 'prefix_66', 5, True),       # below the threshold: one gather
])
def test_uniform_sample_tiled_matches_untiled(b, mask_kind, k, use_meta):
  """The tiled draw is the untiled one, element for element, for any
  mask; and it runs ceil(last valid row / tile rows) tiles."""
  from graphlearn_tpu.ops import neighbor
  rng = np.random.default_rng(b + k)
  n = 500
  indptr, indices = _random_csr(rng, n, 24)
  n_valid = {'none': 0, 'one_row': 1, 'prefix_66': (2 * b) // 3,
             'full': b, 'scattered': b}[mask_kind]
  seed_mask = np.arange(b) < n_valid
  if mask_kind == 'scattered':
    seed_mask = rng.random(b) < 0.5
  seeds = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
  seed_mask = jnp.asarray(seed_mask)
  indptr, indices = jnp.asarray(indptr), jnp.asarray(indices)
  meta = (jnp.stack([indptr[:-1], indptr[1:] - indptr[:-1]], 1)
          if use_meta else None)
  key = jax.random.PRNGKey(b * 31 + k)
  want = _untiled_uniform_sample(indptr, indices, seeds, seed_mask, k, key,
                                 meta=meta)
  got = ops.uniform_sample(indptr, indices, seeds, seed_mask, k, key,
                           meta=meta)
  for g, w, what in zip(got, want, ('nbrs', 'epos', 'mask')):
    assert g.dtype == w.dtype and g.shape == w.shape, what
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w), what)
  assert np.asarray(want[2]).any() == (n_valid > 0)

  tile_rows = neighbor.draw_tile_rows(b)
  if b < 2048:
    assert tile_rows == 0
    return
  # the smallest multiple of 256 that covers the cap in DRAW_TILES tiles
  assert tile_rows % 256 == 0
  assert (tile_rows - 256) * neighbor.DRAW_TILES < b <= \
      tile_rows * neighbor.DRAW_TILES
  *tiled, tiles = neighbor.uniform_sample_tiled(
      indptr, indices, seeds, seed_mask, k, key, meta)
  for g, w in zip(tiled, want):
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
  where = np.flatnonzero(np.asarray(seed_mask))
  last_valid = int(where[-1]) + 1 if where.size else 0
  assert tiles.dtype == jnp.int32
  assert int(tiles) == -(-last_valid // tile_rows)


def test_uniform_sample_tiled_never_reads_for_masked_tiles():
  """Tiles past the last valid row contribute nothing: with every masked
  row's gather target poisoned (today a masked row gathers row 0 of the
  row table and element 0 of ``indices``), the outputs still equal the
  clean graph's and hold no poison — the poison is only ever read inside
  the tiles that ran, where the mask throws it away."""
  from graphlearn_tpu.ops import neighbor
  rng = np.random.default_rng(3)
  n, b, k, poison = 400, 4096, 5, -7
  indptr, indices = _random_csr(rng, n, 12)
  # node 0 is where masked rows look: nobody valid samples from it, and
  # its segment (the head of ``indices``) is poison
  seeds = rng.integers(1, n, b).astype(np.int32)
  poisoned = indices.copy()
  poisoned[:indptr[1]] = poison
  tile_rows = neighbor.draw_tile_rows(b)
  n_valid = 5 * tile_rows + 7                # 6 of 16 tiles begin below it
  assert -(-b // tile_rows) == 16
  seed_mask = jnp.asarray(np.arange(b) < n_valid)
  seeds[n_valid:] = 0
  key = jax.random.PRNGKey(8)
  args = (jnp.asarray(seeds), seed_mask, k, key)
  clean = neighbor.uniform_sample_tiled(
      jnp.asarray(indptr), jnp.asarray(indices), *args)
  got = neighbor.uniform_sample_tiled(
      jnp.asarray(indptr), jnp.asarray(poisoned), *args)
  assert int(got[3]) == 6
  for g, c in zip(got[:3], clean[:3]):
    np.testing.assert_array_equal(np.asarray(g), np.asarray(c))
  nbrs, epos, mask = (np.asarray(x) for x in got[:3])
  assert not (nbrs == poison).any()
  ran = int(got[3]) * tile_rows
  assert mask[:n_valid].any() and not mask[n_valid:].any()
  assert (nbrs[ran:] == ops.FILL).all() and (epos[ran:] == 0).all()


def test_weighted_sample_bias():
  # node 0 -> {1 (w=100), 2 (w=1)}: draws should overwhelmingly pick 1.
  row = np.array([0, 0])
  col = np.array([1, 2])
  topo = Topology(np.stack([row, col]), num_nodes=3,
                  edge_weights=np.array([100.0, 1.0], np.float32))
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  indices = jnp.asarray(topo.indices)
  cum = ops.build_row_cumsum(indptr, jnp.asarray(topo.edge_weights))
  seeds = jnp.zeros((64,), jnp.int32)
  nbrs, _, m = ops.weighted_sample(indptr, indices, cum, seeds,
                                   jnp.ones(64, bool), 1,
                                   jax.random.PRNGKey(3))
  assert bool(m.all())
  picks = np.asarray(nbrs).reshape(-1)
  assert (picks == 1).mean() > 0.9


def test_weighted_sample_keep_all_when_small_degree():
  row = np.array([0, 0])
  col = np.array([1, 2])
  topo = Topology(np.stack([row, col]), num_nodes=3,
                  edge_weights=np.array([1.0, 9.0], np.float32))
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  cum = ops.build_row_cumsum(indptr, jnp.asarray(topo.edge_weights))
  nbrs, _, m = ops.weighted_sample(indptr, jnp.asarray(topo.indices), cum,
                                   jnp.zeros(1, jnp.int32),
                                   jnp.ones(1, bool), 4,
                                   jax.random.PRNGKey(4))
  assert m[0].tolist() == [True, True, False, False]
  assert set(np.asarray(nbrs[0, :2]).tolist()) == {1, 2}


# ---------------------------------------------------------------- membership

def test_edge_in_csr():
  topo = chain_star_topo()
  sorted_idx, _ = ops.sort_csr_segments(topo.indptr, topo.indices)
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  rows = jnp.array([0, 0, 1, 3, 2], dtype=jnp.int32)
  cols = jnp.array([1, 0, 2, 0, 3], dtype=jnp.int32)
  hit = ops.edge_in_csr(indptr, jnp.asarray(sorted_idx), rows, cols)
  assert hit.tolist() == [True, False, True, False, True]


def test_negative_sample():
  topo = chain_star_topo()
  sorted_idx, _ = ops.sort_csr_segments(topo.indptr, topo.indices)
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  rows, cols, mask = ops.random_negative_sample(
      indptr, jnp.asarray(sorted_idx), 4, 4, 8, jax.random.PRNGKey(5),
      trials=8)
  rows, cols, mask = map(np.asarray, (rows, cols, mask))
  edge_set = set(zip(*chain_star_topo().to_coo()))
  edge_set = {(int(r), int(c)) for r, c in zip(*topo.to_coo())}
  for r, c, m in zip(rows, cols, mask):
    if m:
      assert (r, c) not in edge_set


def test_negative_sample_padding_fills():
  # complete digraph on 2 nodes incl self loops -> no negatives exist
  row = np.array([0, 0, 1, 1])
  col = np.array([0, 1, 0, 1])
  topo = Topology(np.stack([row, col]), num_nodes=2)
  sorted_idx, _ = ops.sort_csr_segments(topo.indptr, topo.indices)
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  _, _, mask = ops.random_negative_sample(
      indptr, jnp.asarray(sorted_idx), 2, 2, 4, jax.random.PRNGKey(6),
      trials=2, padding=True)
  assert bool(np.asarray(mask).all())


# ---------------------------------------------------------------- inducer

def test_inducer_two_hops():
  topo = chain_star_topo()
  indptr, indices = dev(topo)
  seeds = jnp.array([0, 0, 1], dtype=jnp.int32)  # dup seed exercises dedup
  state, uniq_seeds, seed_mask, inv = ops.init_node(seeds, jnp.ones(3, bool),
                                                    capacity=32)
  assert int(state.num_nodes) == 2
  assert uniq_seeds[:2].tolist() == [0, 1]
  assert inv.tolist() == [0, 0, 1]  # local index of each original seed

  # hop 1 from frontier [0, 1] (local idx 0, 1)
  frontier = uniq_seeds
  nbrs, epos, m = ops.uniform_sample(indptr, indices, frontier, seed_mask,
                                     3, jax.random.PRNGKey(7))
  src_idx = jnp.arange(3, dtype=jnp.int32)
  state, out = ops.induce_next(state, src_idx, nbrs, m)

  nodes = np.asarray(state.nodes)
  n = int(state.num_nodes)
  # local ids are consistent: nodes[row] -> nodes[col] must be a real edge
  rows, cols, em = (np.asarray(out['rows']), np.asarray(out['cols']),
                    np.asarray(out['edge_mask']))
  edge_set = {(int(r), int(c)) for r, c in zip(*topo.to_coo())}
  for r, c, valid in zip(rows, cols, em):
    if valid:
      assert (nodes[r], nodes[c]) in edge_set
  # frontier contains only newly added nodes, matching num_new
  fmask = np.asarray(out['frontier_mask'])
  fr = np.asarray(out['frontier'])[fmask]
  assert len(fr) == int(out['num_new'])
  assert set(fr.tolist()).isdisjoint({0, 1})
  # every frontier node appears in the node buffer at its frontier_idx
  fidx = np.asarray(out['frontier_idx'])[fmask]
  np.testing.assert_array_equal(nodes[fidx], fr)
  # no duplicates in node buffer
  assert len(set(nodes[:n].tolist())) == n

  # hop 2: sampling from hop-1 frontier keeps global dedup
  state2, out2 = ops.induce_next(
      state, out['frontier_idx'],
      *ops.uniform_sample(indptr, indices, out['frontier'],
                          out['frontier_mask'], 2,
                          jax.random.PRNGKey(8))[::2])
  n2 = int(state2.num_nodes)
  nodes2 = np.asarray(state2.nodes)
  assert len(set(nodes2[:n2].tolist())) == n2


def test_merge_inducer_matches_table_engine():
  """The merge-sort exact inducer and the direct-address table inducer
  implement the same semantics: identical node SETS, identical decoded
  edge multisets, identical counts, on random multi-hop batches (local
  index assignment may differ — 'any winner is correct')."""
  rng = np.random.default_rng(11)
  for trial in range(4):
    n = int(rng.integers(20, 120))
    f, k1, k2 = 6, 4, 3
    # sorted distinct seeds: both engines then assign identical seed
    # slots (merge init = ascending, table init = first occurrence), so
    # hop-1 candidates attribute to the same underlying seed per row
    seeds = jnp.asarray(np.sort(rng.choice(n, f, replace=False))
                        .astype(np.int32))
    smask = jnp.asarray(rng.random(f) < 0.9)
    h1 = jnp.asarray(rng.integers(0, n, (f, k1)).astype(np.int32))
    m1 = jnp.asarray(rng.random((f, k1)) < 0.8)
    cap = f + f * k1 + f * k1 * k2

    st_a, uq_a, um_a, inv_a = ops.init_node_merge(seeds, smask,
                                                  capacity=cap)
    st_b, uq_b, um_b, inv_b = ops.init_node_map(seeds, smask,
                                                capacity=cap,
                                                num_graph_nodes=n)
    # like the real sampler: no candidates for invalid frontier slots
    m1 = m1 & um_a[:, None]
    assert int(st_a.num_nodes) == int(st_b.num_nodes)
    nn0 = int(st_a.num_nodes)
    assert (set(np.asarray(st_a.nodes)[:nn0].tolist())
            == set(np.asarray(st_b.nodes)[:nn0].tolist()))
    # inverse maps each seed to a slot holding that seed's id
    for j in range(f):
      if bool(smask[j]):
        assert int(st_a.nodes[int(inv_a[j])]) == int(seeds[j])

    fidx = jnp.arange(f, dtype=jnp.int32)
    st_a, out_a = ops.induce_next_merge(st_a, fidx, h1, m1, prefix_cap=f)
    st_b, out_b = ops.induce_next_map(st_b, fidx, h1, m1)
    assert int(out_a['num_new']) == int(out_b['num_new'])

    def edge_multiset(st, out):
      nodes = np.asarray(st.nodes)
      r, c = np.asarray(out['rows']), np.asarray(out['cols'])
      em = np.asarray(out['edge_mask'])
      return sorted((int(nodes[a]), int(nodes[b]))
                    for a, b, v in zip(r, c, em) if v)

    assert edge_multiset(st_a, out_a) == edge_multiset(st_b, out_b)

    # second hop from each engine's own frontier
    fr_a, fm_a = out_a['frontier'], out_a['frontier_mask']
    fr_b, fm_b = out_b['frontier'], out_b['frontier_mask']
    assert (set(np.asarray(fr_a)[np.asarray(fm_a)].tolist())
            == set(np.asarray(fr_b)[np.asarray(fm_b)].tolist()))
    w = fr_a.shape[0]
    h2 = jnp.asarray(rng.integers(0, n, (w, k2)).astype(np.int32))
    m2 = jnp.asarray(rng.random((w, k2)) < 0.8)
    # feed both engines the SAME candidates, masked to each frontier
    st_a2, out_a2 = ops.induce_next_merge(
        st_a, out_a['frontier_idx'], h2, m2 & fm_a[:, None],
        prefix_cap=f + f * k1, update_view=False)
    st_b2, out_b2 = ops.induce_next_map(
        st_b, out_b['frontier_idx'], h2, m2 & fm_b[:, None])
    # frontiers may order differently, so compare global sets only
    na, nb = int(st_a2.num_nodes), int(st_b2.num_nodes)
    assert na == nb
    assert (set(np.asarray(st_a2.nodes)[:na].tolist())
            == set(np.asarray(st_b2.nodes)[:nb].tolist()))
    # no duplicates, compact, FILL tail
    va = np.asarray(st_a2.nodes)[:na]
    assert len(set(va.tolist())) == na
    assert (np.asarray(st_a2.nodes)[na:] == -1).all()


def test_merge_inducer_node_budget_truncates_safely():
  """Budget-clamped plans can overflow per-hop caps: the merge engine
  truncates cleanly — num_nodes stays within capacity, earlier entries
  (seeds included) are never corrupted, in-buffer nodes stay
  deduplicated, and the raw per-hop new counts still expose the
  overflow (num_sampled_nodes[i+1] > caps[i+1])."""
  import graphlearn_tpu as glt
  from graphlearn_tpu.sampler import NodeSamplerInput, check_no_overflow
  rng = np.random.default_rng(5)
  n, e = 200, 1600
  rows, cols = rng.integers(0, n, e), rng.integers(0, n, e)
  g = glt.data.Graph(glt.data.Topology(np.stack([rows, cols]),
                                       num_nodes=n), 'CPU')
  s = glt.sampler.NeighborSampler(g, [15, 10], seed=0, dedup='map',
                                  node_budget=24)
  seeds = rng.integers(0, n, 32)
  out = s.sample_from_nodes(NodeSamplerInput(seeds), batch_cap=32)
  node = np.asarray(out.node)
  cap = node.shape[0]
  nn = int(out.num_nodes)
  assert nn <= cap                       # clamped growth invariant
  valid = node[:nn]
  valid = valid[valid >= 0]
  assert len(set(valid.tolist())) == len(valid)
  assert (node[nn:] == -1).all()
  # the seed block survives un-corrupted
  uniq_seeds = sorted(set(seeds.tolist()))
  assert node[:len(uniq_seeds)].tolist() == uniq_seeds
  # a 15-fanout hop from 32 seeds blows a 24-cap: detectable
  assert not check_no_overflow(s, out, batch_cap=32)
  # no mask-valid edge may reference an unstored (truncated) node —
  # models would silently aggregate clamped-garbage rows otherwise
  r, c = np.asarray(out.row), np.asarray(out.col)
  em = np.asarray(out.edge_mask)
  assert em.any()
  assert (r[em] < nn).all() and (c[em] < nn).all()
  assert (r[em] >= 0).all() and (c[em] >= 0).all()


# ---------------------------------------------------------------- subgraph

def test_node_subgraph():
  topo = chain_star_topo()
  indptr, indices = dev(topo)
  srcs = jnp.array([0, 2, 3, 0], dtype=jnp.int32)  # set {0, 2, 3}
  out = ops.node_subgraph(indptr, indices, srcs, jnp.ones(4, bool),
                          max_degree=4)
  assert int(out['num_nodes']) == 3
  nodes = np.asarray(out['nodes'])[:3]
  assert nodes.tolist() == [0, 2, 3]
  rows = np.asarray(out['rows'])
  cols = np.asarray(out['cols'])
  em = np.asarray(out['edge_mask'])
  got = {(nodes[r], nodes[c]) for r, c, v in zip(rows, cols, em) if v}
  # induced edges among {0,2,3}: 0->2, 0->3, 2->3
  assert got == {(0, 2), (0, 3), (2, 3)}


def test_node_subgraph_bucketed_celebrity():
  """One celebrity vertex must not force every row to its degree: the
  bucketed op matches the exact op's edge set while scanning most rows
  only to deg_small."""
  # star: node 0 -> 1..49 (deg 49); chain 1->2->...->49 (deg 1 each)
  n = 50
  rows = np.concatenate([np.zeros(n - 1, np.int64),
                         np.arange(1, n - 1)])
  cols = np.concatenate([np.arange(1, n), np.arange(2, n)])
  order = np.lexsort((cols, rows))
  rows, cols = rows[order], cols[order]
  indptr_np = np.zeros(n + 1, np.int32)
  np.add.at(indptr_np, rows + 1, 1)
  indptr = jnp.asarray(np.cumsum(indptr_np).astype(np.int32))
  indices = jnp.asarray(cols.astype(np.int32))
  srcs = jnp.asarray(np.arange(16, dtype=np.int32))  # {0..15}
  mask = jnp.ones(16, bool)
  exact = ops.node_subgraph(indptr, indices, srcs, mask, max_degree=49)
  buck = ops.node_subgraph_bucketed(indptr, indices, srcs, mask,
                                    deg_small=8, cap_large=4,
                                    max_degree=49)
  assert int(buck['num_dropped_rows']) == 0

  def edge_set(out):
    nodes = np.asarray(out['nodes'])
    return {(int(nodes[r]), int(nodes[c]))
            for r, c, v in zip(np.asarray(out['rows']),
                               np.asarray(out['cols']),
                               np.asarray(out['edge_mask'])) if v}

  es = edge_set(buck)
  assert es == edge_set(exact)
  # the celebrity's edges into the set are all present
  assert {(0, i) for i in range(1, 16)} <= es
  # buffer is the bucketed size, far below B * max_degree
  assert buck['rows'].shape[0] == 16 * 8 + 4 * 49 < 16 * 49

  # overflow reporting: two celebrities, cap_large=1
  rows2 = np.concatenate([rows, np.full(n - 2, n, np.int64)])
  cols2 = np.concatenate([cols, np.arange(1, n - 1)])
  order = np.lexsort((cols2, rows2))
  rows2, cols2 = rows2[order], cols2[order]
  ip = np.zeros(n + 2, np.int32)
  np.add.at(ip, rows2 + 1, 1)
  indptr2 = jnp.asarray(np.cumsum(ip).astype(np.int32))
  indices2 = jnp.asarray(cols2.astype(np.int32))
  srcs2 = jnp.asarray(np.array([0, n, 1, 2], np.int32))
  buck2 = ops.node_subgraph_bucketed(indptr2, indices2, srcs2,
                                     jnp.ones(4, bool), deg_small=2,
                                     cap_large=1, max_degree=49)
  assert int(buck2['num_dropped_rows']) == 1


# ---------------------------------------------------------------- pallas

def test_gather_rows_hbm_interpret():
  """Pallas row-gather kernel vs numpy, via the interpreter (no TPU in
  the test env); exercises non-128-aligned F, duplicate ids, and padding
  of B to the block size."""
  rng = np.random.default_rng(0)
  table = rng.random((97, 100), np.float32)
  tdev = jnp.asarray(table)
  ids = np.array([0, 96, 7, 7, 45, 3, 8, 12, 1, 0, 33], np.int32)
  out = ops.gather_rows_hbm(tdev, jnp.asarray(ids), block_rows=4,
                            interpret=True)
  np.testing.assert_allclose(np.asarray(out), table[ids])
  # fallback path off-TPU without interpret
  out = ops.gather_rows_hbm(tdev, jnp.asarray(ids))
  np.testing.assert_allclose(np.asarray(out), table[ids])
  # out-of-range ids clamp instead of faulting
  out = ops.gather_rows_hbm(tdev, jnp.asarray(np.array([200, -5], np.int32)),
                            block_rows=2, interpret=True)
  np.testing.assert_allclose(np.asarray(out), table[[96, 0]])


def test_gather_rows_hbm_force_misaligned_falls_back():
  """Regression (ISSUE 13): force=True on a misaligned table width used
  to reach Mosaic and fail to lower — force must yield to the 128-lane
  alignment guard (with a warning) and return the bit-identical XLA
  fallback instead. interpret=True keeps honoring force (the Pallas
  interpreter has no lane constraint; the v1 test above relies on it)."""
  import warnings
  rng = np.random.default_rng(2)
  table = rng.random((64, 100), np.float32)     # 100 % 128 != 0
  ids = np.array([3, 0, 63, 17], np.int32)
  for fn in (ops.gather_rows_hbm, ops.gather_rows_hbm2):
    with warnings.catch_warnings(record=True) as wlog:
      warnings.simplefilter('always')
      out = fn(jnp.asarray(table), jnp.asarray(ids), force=True)
    assert any('128-lane' in str(w.message) for w in wlog), fn
    np.testing.assert_array_equal(np.asarray(out), table[ids])


def test_plan_gather_runs_covers_every_slot_exactly_once():
  """The v2 DMA plan is a partition: every slot is written by exactly
  one copy — its own single, or the full-span run that starts at most
  run_span-1 slots before it (and full runs never cross a block
  boundary, never leave the table, and carry strictly consecutive
  ids)."""
  rng = np.random.default_rng(3)
  n, block_rows, span = 500, 16, 4
  for trial in range(5):
    ids = np.sort(rng.integers(0, n, 64)).astype(np.int32)
    if trial == 4:     # fully contiguous best case
      ids = np.arange(100, 164, dtype=np.int32)
    plan = np.asarray(ops.plan_gather_runs(jnp.asarray(ids), n,
                                           block_rows, span))
    kind, row = ops.decode_gather_plan(plan)
    assert set(np.unique(kind)) <= {0, 1, 2}   # sign-bit-safe decode
    np.testing.assert_array_equal(row, ids)
    writes = np.zeros(ids.shape[0], np.int64)
    for j, kd in enumerate(kind):
      if kd == 0:
        writes[j] += 1
      elif kd == 1:
        assert j % block_rows + span <= block_rows   # stays in block
        assert ids[j] + span <= n                    # stays in table
        np.testing.assert_array_equal(                # consecutive rows
            ids[j:j + span], ids[j] + np.arange(span))
        writes[j:j + span] += 1
    np.testing.assert_array_equal(writes, 1)
    if trial == 4:
      # the contiguous case must actually produce run coverage, and
      # covered slots must decode as _KIND_COVERED (regression: kind 2
      # rides the int32 sign bit — a bare >> 30 read it as -2)
      assert (kind == 1).any() and (kind == 2).any()


def test_gather_rows_hbm2_interpret_parity():
  """v2 kernel vs jnp.take through the interpreter: dtypes f32/bf16/
  int32, ragged (non-block-multiple) id vectors, duplicate-heavy and
  sorted-adversarial distributions, presorted fast path, out-of-range
  clamping."""
  rng = np.random.default_rng(4)
  n, f = 300, 128
  tables = {
      'f32': rng.standard_normal((n, f)).astype(np.float32),
      'bf16': jnp.asarray(rng.standard_normal((n, f)),
                          dtype=jnp.bfloat16),
      'int32': rng.integers(-5000, 5000, (n, f)).astype(np.int32),
  }
  id_sets = {
      'random-ragged': rng.integers(0, n, 37).astype(np.int32),
      'dup-heavy': np.repeat(rng.integers(0, n, 6), 7).astype(np.int32),
      # sorted-adversarial: ascending but with gaps and stutters, so
      # run detection sees every edge case (gap, dup, exact span)
      'sorted-adversarial': np.sort(np.concatenate(
          [np.arange(40, 52), [52, 52, 52], np.arange(200, 204),
           rng.integers(0, n, 13)])).astype(np.int32),
      'contig': np.arange(17, 81, dtype=np.int32),
  }
  for tname, table in tables.items():
    tdev = jnp.asarray(table)
    ref_np = np.asarray(tdev)
    for iname, ids in id_sets.items():
      if tname != 'f32' and iname in ('dup-heavy', 'contig'):
        continue   # dtype coverage x 2 dists suffices; each extra
        # (dtype, id-shape) pair compiles its own interpret kernel and
        # the tier-1 wall budget is a guarded resource (conftest canary)
      out = ops.gather_rows_hbm2(tdev, jnp.asarray(ids), block_rows=16,
                                 run_span=4, interpret=True)
      np.testing.assert_array_equal(np.asarray(out), ref_np[ids]), \
          (tname, iname)
      if tname == 'f32' and (np.diff(ids) >= 0).all():
        out = ops.gather_rows_hbm2(tdev, jnp.asarray(ids),
                                   block_rows=16, run_span=4,
                                   presorted=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), ref_np[ids])
  # clamping matches take's contract (same as v1)
  t = jnp.asarray(tables['f32'])
  out = ops.gather_rows_hbm2(t, jnp.asarray(np.array([900, -3], np.int32)),
                             block_rows=4, run_span=2, interpret=True)
  np.testing.assert_array_equal(np.asarray(out),
                                np.asarray(t)[[n - 1, 0]])


def _fused_hop_csr(rng, n, e, hub_deg=0):
  rows = rng.integers(0, n, e)
  if hub_deg:
    rows = np.concatenate([np.zeros(hub_deg, np.int64), rows])
  cols = rng.integers(0, n, rows.shape[0])
  order = np.lexsort((cols, rows))
  rows, cols = rows[order], cols[order]
  indptr = np.concatenate(
      [[0], np.cumsum(np.bincount(rows, minlength=n))]).astype(np.int32)
  return jnp.asarray(indptr), jnp.asarray(cols.astype(np.int32))


def test_sample_hop_fused_interpret_parity():
  """Fused sample+gather hop vs ops.uniform_sample, bit for bit, under
  the SAME key: uniform degrees, deg <= k keep-all, masked seeds, and a
  hub whose degree exceeds the staged window (the per-sample row-DMA
  path) — across windows and both meta/indptr row lookups."""
  rng = np.random.default_rng(5)
  n = 150
  ip, ind = _fused_hop_csr(rng, n, 1200, hub_deg=700)
  meta = jnp.stack([ip[:-1], ip[1:] - ip[:-1]], 1).astype(jnp.int32)
  for window in (128, 256):
    blocks = ops.build_indices128(ind, min_rows=window // 128 + 1)
    for trial, k in ((0, 5), (1, 12)):
      key = jax.random.fold_in(jax.random.PRNGKey(1), trial)
      seeds = jnp.asarray(np.concatenate(
          [[0], rng.integers(0, n, 23)]).astype(np.int32))
      mask = jnp.asarray(rng.random(24) < 0.85)
      # indptr-lookup variant once (window 128 only): each extra config
      # compiles its own interpret kernel — tier-1 wall budget
      metas = (meta, None) if window == 128 and k == 5 else (meta,)
      for m in metas:
        ref = ops.uniform_sample(ip, ind, seeds, mask, k, key, meta=m)
        got = ops.sample_hop_fused(ip, ind, blocks, seeds, mask, k, key,
                                   meta=m, window=window, block_seeds=8,
                                   interpret=True)
        for a, b, what in zip(ref, got, ('nbrs', 'epos', 'mask')):
          np.testing.assert_array_equal(
              np.asarray(a), np.asarray(b)), (window, k, what)


@pytest.mark.slow  # tier-1 budget (PR 18): counter-stream variant of
# test_sample_hop_fused_interpret_parity, which stays tier-1
def test_sample_hop_fused_stream_matches_sampler_counters():
  """Same fold_in counters -> identical edges: a NeighborSampler with
  use_fused_hop='interpret' (kernel exercised through the Pallas
  interpreter INSIDE the fused multi-hop program) replays the plain
  sampler's stream bit for bit across batches — nodes, edges, masks,
  and the host key counter (GLT_STRICT arms the transfer guards via
  conftest for this suite's env)."""
  import graphlearn_tpu as glt
  from graphlearn_tpu.sampler import NodeSamplerInput
  rng = np.random.default_rng(6)
  n, e = 200, 3000
  rows, cols = rng.integers(0, n, e), rng.integers(0, n, e)
  g = glt.data.Graph(glt.data.Topology(np.stack([rows, cols]),
                                       num_nodes=n), 'CPU')
  for dedup in ('merge', 'tree'):
    s_ref = glt.sampler.NeighborSampler(g, [4, 3], seed=11, dedup=dedup,
                                        with_edge=True)
    s_fh = glt.sampler.NeighborSampler(g, [4, 3], seed=11, dedup=dedup,
                                       with_edge=True,
                                       use_fused_hop='interpret',
                                       fused_hop_window=128)
    for _ in range(3):
      seeds = rng.integers(0, n, 16)
      a = s_ref.sample_from_nodes(NodeSamplerInput(seeds), batch_cap=16)
      b = s_fh.sample_from_nodes(NodeSamplerInput(seeds), batch_cap=16)
      for field in ('node', 'row', 'col', 'edge', 'edge_mask'):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)))
    assert s_ref._call_count == s_fh._call_count


# ---------------------------------------------------------------- stitch

def test_stitch_rows():
  idx0 = jnp.array([2, 0], dtype=jnp.int32)
  rows0 = jnp.array([[10, 11], [20, ops.FILL]], dtype=jnp.int32)
  m0 = jnp.array([[True, True], [True, False]])
  idx1 = jnp.array([1], dtype=jnp.int32)
  rows1 = jnp.array([[30, 31]], dtype=jnp.int32)
  m1 = jnp.array([[True, True]])
  out, om = ops.stitch_rows([idx0, idx1], [rows0, rows1], [m0, m1], 3)
  assert out[2].tolist() == [10, 11]
  assert out[0, 0].tolist() == 20
  assert om[0].tolist() == [True, False]
  assert out[1].tolist() == [30, 31]


def test_trace_parsers_shared_loader(tmp_path):
  """device_program_ms / device_op_ms parse the same trace through the
  shared memoized loader: program averages, op totals with '.NNN'
  stripping (bare-digit names intact), steps normalization."""
  import gzip
  import json
  from graphlearn_tpu.utils import device_op_ms, device_program_ms
  events = [
      {'ph': 'M', 'name': 'process_name', 'pid': 1,
       'args': {'name': 'TPU:0'}},
      {'ph': 'M', 'name': 'process_name', 'pid': 2,
       'args': {'name': 'CPU'}},
      # programs: two calls of the same jit program
      {'ph': 'X', 'pid': 1, 'name': 'jit_train_step(123)', 'dur': 2000,
       'ts': 0},
      {'ph': 'X', 'pid': 1, 'name': 'jit_train_step(123)', 'dur': 4000,
       'ts': 10},
      # ops: suffix-stripped grouping; bare-digit name kept whole
      {'ph': 'X', 'pid': 1, 'name': 'fusion.7', 'dur': 1000, 'ts': 1},
      {'ph': 'X', 'pid': 1, 'name': 'fusion.8', 'dur': 3000, 'ts': 2},
      {'ph': 'X', 'pid': 1, 'name': 'layer1', 'dur': 500, 'ts': 3},
      # non-TPU lane must be ignored
      {'ph': 'X', 'pid': 2, 'name': 'fusion.9', 'dur': 9000, 'ts': 4},
      # a v5e trace repeats the device time on a 'Steps' lane under
      # step-number names: named lanes other than 'XLA Ops' are not ops
      {'ph': 'M', 'name': 'thread_name', 'pid': 1, 'tid': 3,
       'args': {'name': 'Steps'}},
      {'ph': 'X', 'pid': 1, 'tid': 3, 'name': '7', 'dur': 7000, 'ts': 0},
  ]
  d = tmp_path / 'plugins' / 'profile' / 'run'
  d.mkdir(parents=True)
  with gzip.open(d / 'host.trace.json.gz', 'wt') as f:
    json.dump({'traceEvents': events}, f)
  progs = device_program_ms(str(tmp_path))
  assert progs == {'jit_train_step(123)': (3.0, 2)}   # avg of 2, 4 ms
  ops = device_op_ms(str(tmp_path), steps=2)
  assert ops['fusion'] == (2.0, 2)     # (1+3) ms total / 2 steps
  assert ops['layer1'] == (0.25, 1)    # bare digits NOT stripped
  assert 'fusion.9' not in ops and 'jit_train_step(123)' not in ops
  assert '7' not in ops
  top = device_op_ms(str(tmp_path), top=1, steps=2)
  assert list(top) == ['fusion']


def test_build_padded_adjacency_device_contract():
  """Device padded-table builder == host builder's contract: every
  entry is a real neighbor, rows are duplicate-free uniform subsets of
  size min(deg, W), epos maps back to CSR positions, and a new key
  yields a different subset for truncated rows (the per-epoch
  de-bias)."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu import ops
  rng = np.random.default_rng(0)
  n, W = 50, 4
  # heavy row 0 (degree 20), plus random rows incl. some zero-degree
  rows = np.concatenate([np.zeros(20, np.int64),
                         rng.integers(1, n // 2, 150)])
  cols = rng.integers(0, n, rows.shape[0])
  # dedup (v, w) pairs so subsets are over distinct neighbors
  pairs = np.unique(np.stack([rows, cols], 1), axis=0)
  rows, cols = pairs[:, 0], pairs[:, 1]
  order = np.argsort(rows, kind='stable')
  rows, cols = rows[order], cols[order]
  indptr = np.concatenate([[0], np.cumsum(np.bincount(rows,
                                                      minlength=n))])
  tab, deg, epos = ops.build_padded_adjacency_device(
      jnp.asarray(indptr), jnp.asarray(cols), W, jax.random.PRNGKey(0),
      edge_pos=True)
  tab, deg, epos = np.asarray(tab), np.asarray(deg), np.asarray(epos)
  true_deg = np.diff(indptr)
  np.testing.assert_array_equal(deg, np.minimum(true_deg, W))
  for v in range(n):
    got = tab[v][tab[v] != ops.FILL]
    nbrs = set(cols[indptr[v]:indptr[v + 1]].tolist())
    assert len(got) == min(true_deg[v], W)
    assert len(set(got.tolist())) == len(got)        # no duplicates
    assert set(got.tolist()) <= nbrs                 # real neighbors
    for j in range(len(got)):                        # epos round-trips
      assert cols[epos[v, j]] == tab[v, j]
  # reseed changes the heavy row's subset (21 choose 4 collisions are
  # vanishingly unlikely across 5 keys)
  subsets = set()
  for s in range(5):
    t2, _, _ = ops.build_padded_adjacency_device(
        jnp.asarray(indptr), jnp.asarray(cols), W,
        jax.random.PRNGKey(s), edge_pos=False)
    subsets.add(tuple(sorted(np.asarray(t2)[0].tolist())))
  assert len(subsets) > 1
