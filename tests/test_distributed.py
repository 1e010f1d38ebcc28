"""Distributed layer tests on the virtual CPU mesh.

Mirrors the reference's key fixture (test/python/dist_test_utils.py:38-95):
a deterministic 40-node ring graph split into 2 partitions with analytic
partition books (node_pb = v % 2) so assertions can compute expected
values. Multi-node is simulated as multi-device (conftest forces 8 CPU
devices)."""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.typing import FeaturePartitionData, GraphPartitionData

N = 40


def ring_fixture(num_parts=2):
  """Ring v -> v+1, v -> v+2 (mod N); node_pb = v % num_parts; features
  feat[v] = v (so cross-partition gathers are checkable)."""
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  eids = np.arange(2 * N)
  node_pb = (np.arange(N) % num_parts).astype(np.int32)
  edge_pb = node_pb[rows]
  parts, feats = [], []
  for p in range(num_parts):
    m = edge_pb == p
    parts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]), eids=eids[m]))
    ids = np.nonzero(node_pb == p)[0]
    feats.append((ids.astype(np.int64),
                  ids[:, None].astype(np.float32) * np.ones((1, 4),
                                                            np.float32)))
  return parts, feats, node_pb, edge_pb


def make_mesh(num_parts):
  import jax
  from jax.sharding import Mesh
  return Mesh(np.array(jax.devices()[:num_parts]), ('g',))


@pytest.mark.parametrize('num_parts', [2, 4])
def test_dist_graph_local_csr(num_parts):
  parts, _, node_pb, edge_pb = ring_fixture(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  # every owned row is present with degree 2
  for p in range(num_parts):
    owned = np.nonzero(node_pb == p)[0]
    rid = dg.row_ids[p]
    valid = rid != np.iinfo(np.int32).max
    np.testing.assert_array_equal(np.sort(rid[valid]), owned)
  np.testing.assert_array_equal(dg.get_node_partitions([0, 1, 2]),
                                [0, 1, 2 % num_parts])


def test_dist_feature_gather():
  num_parts = 2
  _, feats, node_pb, _ = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh)
  # each shard requests a mix of local and remote ids
  ids = np.array([[0, 1, 2, 3], [4, 5, 6, 7]], np.int32)
  out = np.asarray(df.get(ids))
  assert out.shape == (2, 4, 4)
  np.testing.assert_allclose(out[..., 0], ids.astype(np.float32))
  # host path agrees
  np.testing.assert_allclose(df.cpu_get(ids.reshape(-1))[:, 0],
                             ids.reshape(-1))


@pytest.mark.parametrize('with_edge', [False, True])
def test_dist_sampler_ring(with_edge):
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(
      dg, [2, 2], mesh, with_edge=with_edge, seed=0)
  seeds = np.array([[0, 4], [1, 5]], np.int32)  # per-shard seed blocks
  out = sampler.sample_from_nodes(seeds)

  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  emask = np.asarray(out.edge_mask)
  assert node.shape[0] == num_parts
  for p in range(num_parts):
    nn = int(np.asarray(out.num_nodes)[p])
    nodes_p = node[p]
    # seeds lead the node list
    assert set(nodes_p[:2].tolist()) == set(seeds[p].tolist())
    # the ring is deterministic: every sampled edge (neighbor=row, seed=col)
    # must satisfy neighbor = seed+1 or seed+2 (mod N)
    for r, c, m in zip(row[p], col[p], emask[p]):
      if not m:
        continue
      u = int(nodes_p[c])   # sampling seed
      v = int(nodes_p[r])   # its neighbor
      assert v in ((u + 1) % N, (u + 2) % N)
    # all valid nodes unique
    valid = nodes_p[:nn]
    assert len(set(valid.tolist())) == nn
  if with_edge:
    edge = np.asarray(out.edge)
    for p in range(num_parts):
      for e, r, c, m in zip(edge[p], row[p], col[p], emask[p]):
        if not m:
          continue
        u, v = int(node[p][c]), int(node[p][r])
        # eid e encodes edge (u -> v): eids 0..N-1 are +1 edges, N..2N-1 +2
        if e < N:
          assert u == e and v == (e + 1) % N
        else:
          assert u == e - N and v == (e - N + 2) % N


def test_dist_loader_end_to_end():
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  ctx = glt.distributed.init_worker_group(
      num_partitions=num_parts,
      devices=[d for d in mesh.devices.flat])
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, ctx.mesh)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df,
                                   node_labels=np.arange(N) % 4)
  loader = glt.distributed.DistNeighborLoader(
      ds, [2, 2], np.arange(N), batch_size=4, shuffle=True, seed=0,
      mesh=ctx.mesh)
  steps = 0
  for batch in loader:
    steps += 1
    assert np.asarray(batch.node).shape[0] == num_parts
    x = np.asarray(batch.x)
    node = np.asarray(batch.node)
    y = np.asarray(batch.y)
    for p in range(num_parts):
      nn = int(np.asarray(batch.num_nodes)[p])
      # features fetched across shards match global ids
      np.testing.assert_allclose(x[p, :nn, 0], node[p, :nn])
      np.testing.assert_array_equal(y[p, :nn], node[p, :nn] % 4)
  assert steps == len(loader) == N // (num_parts * 4)


def test_dist_dataset_load_from_partition_dir(tmp_path):
  # write a partition dir with the random partitioner, then load + sample
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  feat = np.arange(N, dtype=np.float32)[:, None] * np.ones((1, 4),
                                                           np.float32)
  glt.partition.RandomPartitioner(
      str(tmp_path), 2, N, np.stack([rows, cols]), node_feat=feat,
      seed=0).partition()
  mesh = make_mesh(2)
  ds = glt.distributed.DistDataset().load(
      str(tmp_path), mesh=mesh, node_labels=np.arange(N) % 3)
  loader = glt.distributed.DistNeighborLoader(
      ds, [2], np.arange(N), batch_size=4, seed=0, mesh=mesh)
  batch = next(iter(loader))
  x = np.asarray(batch.x)
  node = np.asarray(batch.node)
  for p in range(2):
    nn = int(np.asarray(batch.num_nodes)[p])
    np.testing.assert_allclose(x[p, :nn, 0], node[p, :nn])


def test_route_overflow_counter():
  """ops.route_slots reports overflow instead of losing it silently."""
  import jax.numpy as jnp
  from graphlearn_tpu import ops
  dest = jnp.zeros((8,), jnp.int32)          # everything to bucket 0
  mask = jnp.ones((8,), bool)
  slot, ok, nov = ops.route_slots(dest, mask, capacity=3,
                                  with_overflow=True)
  assert int(nov) == 5
  assert int(ok.sum()) == 3
  # frontier-width capacity can never overflow
  _, ok, nov = ops.route_slots(dest, mask, capacity=8, with_overflow=True)
  assert int(nov) == 0 and bool(ok.all())


@pytest.mark.parametrize('bucket_frac', [None, 2.0, 0.25])
def test_dist_sampler_bucket_frac_loss_free(bucket_frac):
  """Sub-frontier exchange buckets (capacity = frac * frontier / P with
  the replicated full-width fallback) keep the loss-free contract at
  every fraction: on the ring (deg 2, fanout 2, keep-all) every seed
  yields exactly 2 valid edges, and all decode to real ring edges.
  frac=0.25 at P=4 forces the overflow fallback path to run."""
  num_parts = 4
  parts, _, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(
      dg, [2, 2], mesh, seed=0, bucket_frac=bucket_frac)
  b = 8
  seeds = np.arange(num_parts * b, dtype=np.int32).reshape(num_parts, b)
  out = sampler.sample_from_nodes(seeds)
  em = np.asarray(out.edge_mask)
  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  for p in range(num_parts):
    # hop 1: exactly 2 edges per seed (keep-all); hop 2 adds more
    assert int(em[p].sum()) >= 2 * b, (bucket_frac, int(em[p].sum()))
    for r, c, m in zip(row[p], col[p], em[p]):
      if not m:
        continue
      u, v = int(node[p][c]), int(node[p][r])
      assert v in ((u + 1) % N, (u + 2) % N), (bucket_frac, u, v)


@pytest.mark.parametrize('bucket_frac', [2.0, 0.25])
def test_dist_sampler_two_axis_mesh(bucket_frac):
  """The same sampling program runs on a 2-axis (slice, chip) mesh —
  the multi-slice layout: the hierarchical 2-stage exchange transposes
  full-width along 'chip' (ICI) and fractionally along 'slice' (DCN),
  with a replicated flat fallback on overflow. frac=0.25 forces the
  fractional DCN capacity (and on skewed hops the fallback); both must
  preserve the ring invariants. Feature collection runs over the same
  mesh."""
  import jax
  from jax.sharding import Mesh
  num_parts = 8
  if len(jax.devices()) < num_parts:
    pytest.skip('needs 8 devices')
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = Mesh(np.array(jax.devices()[:num_parts]).reshape(2, 4),
              ('slice', 'chip'))
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh)
  sampler = glt.distributed.DistNeighborSampler(
      dg, [2, 2], mesh, seed=0, dist_feature=df, collect_features=True,
      bucket_frac=bucket_frac)
  b = 4
  seeds = np.arange(num_parts * b, dtype=np.int32).reshape(num_parts, b)
  out = sampler.sample_from_nodes(seeds)
  x, _ = sampler.collate(out)
  node = np.asarray(out.node).reshape(num_parts, -1)
  row = np.asarray(out.row).reshape(num_parts, -1)
  col = np.asarray(out.col).reshape(num_parts, -1)
  em = np.asarray(out.edge_mask).reshape(num_parts, -1)
  fx = np.asarray(x).reshape(num_parts, node.shape[1], -1)
  for p in range(num_parts):
    assert em[p].sum() > 0
    for r, c, m in zip(row[p], col[p], em[p]):
      if not m:
        continue
      u, v = int(node[p][c]), int(node[p][r])
      assert v in ((u + 1) % N, (u + 2) % N), (u, v)
    valid = node[p] >= 0
    np.testing.assert_allclose(fx[p][valid][:, 0], node[p][valid])


def test_dist_sampler_skewed_partition_book_no_loss():
  """Pathologically skewed node_pb (every node owned by partition 0):
  the frontier-width bucket capacity guarantees zero sample loss — every
  valid seed yields min(degree, k) edges (reference contract: the exact
  split never drops, dist_neighbor_sampler.py:585-648)."""
  num_parts = 2
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  eids = np.arange(2 * N)
  node_pb = np.zeros(N, np.int32)            # ALL nodes on partition 0
  parts = [GraphPartitionData(edge_index=np.stack([rows, cols]),
                              eids=eids),
           GraphPartitionData(edge_index=np.zeros((2, 0), np.int64),
                              eids=np.zeros((0,), np.int64))]
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb)
  sampler = glt.distributed.DistNeighborSampler(dg, [2], mesh, seed=0)
  b = 8
  seeds = np.arange(2 * b, dtype=np.int32).reshape(num_parts, b)
  out = sampler.sample_from_nodes(seeds)
  em = np.asarray(out.edge_mask)
  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  for p in range(num_parts):
    # ring degree is 2, fanout 2 -> keep-all: exactly 2 edges per seed,
    # even though every request funnels to shard 0
    assert int(em[p].sum()) == 2 * b, int(em[p].sum())
    for r, c, m in zip(row[p], col[p], em[p]):
      if not m:
        continue
      u, v = int(node[p][c]), int(node[p][r])
      assert v in ((u + 1) % N, (u + 2) % N)


# ------------------------------------------------------------ link + subgraph

def test_dist_sampler_tree_mode():
  """dedup='tree' in the sharded engine: positional slots, exchange hops
  unchanged, edges still valid ring edges."""
  num_parts = 2
  parts, _, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(dg, [2, 2], mesh, seed=0,
                                                dedup='tree')
  seeds = np.array([[0, 4], [1, 5]], np.int32)
  out = sampler.sample_from_nodes(seeds)
  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  em = np.asarray(out.edge_mask)
  for p in range(num_parts):
    np.testing.assert_array_equal(node[p][:2], seeds[p])
    assert em[p].sum() > 0
    for r, c, m in zip(row[p], col[p], em[p]):
      if not m:
        continue
      u, v = int(node[p][c]), int(node[p][r])
      assert v in ((u + 1) % N, (u + 2) % N)
    # every sampled edge creates exactly one new slot
    nn = int(np.asarray(out.num_nodes)[p])
    assert nn == int(em[p].sum()) + 2


def test_dist_hetero_sampler_tree_mode():
  """dedup='tree' in the typed sharded engine: per-type positional
  slots; edges still satisfy the fixture invariants."""
  num_parts = 2
  parts, _, node_pb, (et1, et2) = hetero_ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)
  sampler = glt.distributed.DistNeighborSampler(
      dg, {et1: [2, 2], et2: [1, 1]}, mesh, seed=0, dedup='tree')
  seeds = np.arange(4, dtype=np.int32).reshape(num_parts, 2)
  out = sampler.sample_from_nodes(('u', seeds))
  rev1 = glt.typing.reverse_edge_type(et1)
  rev2 = glt.typing.reverse_edge_type(et2)
  nu = np.asarray(out.node['u'])
  nv = np.asarray(out.node['v'])
  for p in range(num_parts):
    np.testing.assert_array_equal(nu[p][:2], seeds[p])
    r = np.asarray(out.row[rev1])[p]
    c = np.asarray(out.col[rev1])[p]
    m = np.asarray(out.edge_mask[rev1])[p]
    assert m.sum() > 0
    for ri, ci in zip(r[m], c[m]):
      u, v = int(nu[p][ci]), int(nv[p][ri])
      assert v in (u, (u + 1) % N)
    r = np.asarray(out.row[rev2])[p]
    c = np.asarray(out.col[rev2])[p]
    m = np.asarray(out.edge_mask[rev2])[p]
    for ri, ci in zip(r[m], c[m]):
      v, u = int(nv[p][ci]), int(nu[p][ri])
      assert u == (v + 2) % N


def test_dist_link_sampler_binary():
  from graphlearn_tpu.sampler import EdgeSamplerInput, NegativeSampling
  num_parts = 2
  parts, _, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(dg, [2, 2], mesh, seed=0)
  rows = np.array([[0, 4], [1, 5]], np.int32)
  cols = (rows + 1) % N
  out = sampler.sample_from_edges(EdgeSamplerInput(
      rows, cols, neg_sampling=NegativeSampling('binary', 1)))
  node = np.asarray(out.node)
  eli = np.asarray(out.metadata['edge_label_index'])
  label = np.asarray(out.metadata['edge_label'])
  b = 2
  assert eli.shape == (num_parts, 2, 2 * b)
  for p in range(num_parts):
    # positives relocate to the original seed pairs
    for i in range(b):
      assert node[p][eli[p, 0, i]] == rows[p, i]
      assert node[p][eli[p, 1, i]] == cols[p, i]
    # negatives: src is shard-local, and (src, dst) is a true non-edge
    # here because each node's out-edges are all owned by its partition
    for i in range(b, 2 * b):
      u = int(node[p][eli[p, 0, i]])
      v = int(node[p][eli[p, 1, i]])
      assert v not in ((u + 1) % N, (u + 2) % N), (u, v)
    np.testing.assert_array_equal(label[p], [1, 1, 0, 0])


def test_dist_link_negatives_strict():
  """neg_strict=True on a dense graph: every mask-VALID negative pair is
  guaranteed a non-edge (the shard-local check is complete because each
  node's out-edges live on its owner's shard), while slip-through slots
  turn invalid instead of emitting edges. Non-strict on the same dense
  graph emits at least one edge as a 'negative' — proving the flag
  changes behavior."""
  from graphlearn_tpu.sampler import EdgeSamplerInput, NegativeSampling
  num_parts = 2
  n = 8
  # near-complete directed graph: all (u, v) with v != u except (u, u+4)
  rows_l, cols_l = [], []
  for u in range(n):
    for v in range(n):
      if v != u and v != (u + 4) % n:
        rows_l.append(u)
        cols_l.append(v)
  rows, cols = np.array(rows_l), np.array(cols_l)
  adj = set(zip(rows.tolist(), cols.tolist()))
  node_pb = (np.arange(n) % num_parts).astype(np.int32)
  edge_pb = node_pb[rows]
  parts = []
  for p in range(num_parts):
    m = edge_pb == p
    parts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]),
        eids=np.arange(rows.shape[0])[m]))
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  seed_r = np.array([[0, 2], [1, 3]], np.int32)
  seed_c = (seed_r + 1) % n

  def negatives(strict, seed):
    sampler = glt.distributed.DistNeighborSampler(
        dg, [2], mesh, seed=seed, neg_strict=strict)
    got = []
    for trial in range(6):
      out = sampler.sample_from_edges(EdgeSamplerInput(
          seed_r, seed_c, neg_sampling=NegativeSampling('binary', 4)))
      node = np.asarray(out.node)
      eli = np.asarray(out.metadata['edge_label_index'])
      for p in range(num_parts):
        for i in range(2, 10):          # the negative block
          s, d = eli[p, 0, i], eli[p, 1, i]
          if s < 0 or d < 0:
            continue                     # strict-invalidated slot
          u, v = int(node[p][s]), int(node[p][d])
          if u < 0 or v < 0:
            continue
          got.append((u, v))
    return got

  # the strict guarantee must hold for EVERY stream; the loose
  # slip-through is probabilistic (~22% per draw), so scan a few seeds —
  # a single fixed seed makes the assertion a coin flip against each jax
  # version's PRNG stream (it lost on 0.4.x)
  seeds = (3, 7, 11, 19, 23)
  slipped = False
  for s in seeds:
    strict_pairs = negatives(True, s)
    assert strict_pairs, 'strict sampler produced no valid negatives'
    for u, v in strict_pairs:
      assert (u, v) not in adj, (u, v)
    slipped = slipped or any(p in adj for p in negatives(False, s))
    if slipped:
      break
  assert slipped, \
      'expected at least one slipped edge in non-strict mode'


def test_dist_link_sampler_triplet():
  from graphlearn_tpu.sampler import EdgeSamplerInput, NegativeSampling
  num_parts = 2
  parts, _, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(dg, [2], mesh, seed=1)
  rows = np.array([[0, 4], [1, 5]], np.int32)
  cols = (rows + 2) % N
  out = sampler.sample_from_edges(EdgeSamplerInput(
      rows, cols, neg_sampling=NegativeSampling('triplet', 2)))
  node = np.asarray(out.node)
  si = np.asarray(out.metadata['src_index'])
  dp = np.asarray(out.metadata['dst_pos_index'])
  dn = np.asarray(out.metadata['dst_neg_index'])
  assert dn.shape == (num_parts, 4)
  for p in range(num_parts):
    np.testing.assert_array_equal(node[p][si[p]], rows[p])
    np.testing.assert_array_equal(node[p][dp[p]], cols[p])
    # negative dsts are real node ids present in the batch
    assert (dn[p] >= 0).all()
    assert (node[p][dn[p]] >= 0).all()


def test_dist_hetero_link_sampler():
  from graphlearn_tpu.sampler import EdgeSamplerInput, NegativeSampling
  num_parts = 2
  parts, _, node_pb, (et1, et2) = hetero_ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)
  sampler = glt.distributed.DistNeighborSampler(
      dg, {et1: [2], et2: [1]}, mesh, seed=0)
  rows = np.array([[0, 4], [1, 5]], np.int32)
  cols = rows.copy()   # u_i -> v_i are real et1 edges
  out = sampler.sample_from_edges(EdgeSamplerInput(
      rows, cols, input_type=et1,
      neg_sampling=NegativeSampling('binary', 1)))
  nu = np.asarray(out.node['u'])
  nv = np.asarray(out.node['v'])
  eli = np.asarray(out.metadata['edge_label_index'])
  for p in range(num_parts):
    for i in range(2):
      assert nu[p][eli[p, 0, i]] == rows[p, i]
      assert nv[p][eli[p, 1, i]] == cols[p, i]
  np.testing.assert_array_equal(
      np.asarray(out.metadata['edge_label'])[0], [1, 1, 0, 0])
  # triplet mode
  out = sampler.sample_from_edges(EdgeSamplerInput(
      rows, cols, input_type=et1,
      neg_sampling=NegativeSampling('triplet', 1)))
  nu = np.asarray(out.node['u'])
  nv = np.asarray(out.node['v'])
  si = np.asarray(out.metadata['src_index'])
  dp = np.asarray(out.metadata['dst_pos_index'])
  for p in range(num_parts):
    np.testing.assert_array_equal(nu[p][si[p]], rows[p])
    np.testing.assert_array_equal(nv[p][dp[p]], cols[p])


def test_dist_link_negatives_empty_shard():
  """A shard owning ZERO rows of the seed edge type must emit masked-out
  negatives, not INT_MAX padding ids (ops.random_negative_sample_local's
  validity contract)."""
  from graphlearn_tpu.sampler import EdgeSamplerInput, NegativeSampling
  num_parts = 2
  # all edges owned by partition 0: node_pb sends every src to 0
  rows = np.arange(N)
  cols = (np.arange(N) + 1) % N
  node_pb = np.zeros(N, np.int32)
  parts = [GraphPartitionData(edge_index=np.stack([rows, cols]),
                              eids=np.arange(N)),
           GraphPartitionData(edge_index=np.zeros((2, 0), np.int64),
                              eids=np.zeros((0,), np.int64))]
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb)
  sampler = glt.distributed.DistNeighborSampler(dg, [2], mesh, seed=0)
  seed_r = np.array([[0, 2], [4, 6]], np.int32)
  seed_c = (seed_r + 1) % N
  out = sampler.sample_from_edges(EdgeSamplerInput(
      seed_r, seed_c, neg_sampling=NegativeSampling('binary', 1)))
  node = np.asarray(out.node)
  eli = np.asarray(out.metadata['edge_label_index'])
  big = np.iinfo(np.int32).max
  # no INT_MAX id anywhere in either shard's node buffer
  assert (node < big).all()
  # shard 1 owns no rows: its negative slots are masked (-1 indices)
  assert (eli[1, :, 2:] == -1).all()
  # shard 0 has valid negatives
  assert (eli[0, :, 2:] >= 0).all()


def test_dist_subgraph():
  num_parts = 2
  parts, _, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(dg, None, mesh, seed=0,
                                                with_edge=True)
  seeds = np.array([[0, 1, 2, 10], [3, 4, 5, 11]], np.int32)
  out = sampler.subgraph(seeds)
  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  em = np.asarray(out.edge_mask)
  edge = np.asarray(out.edge)
  mapping = np.asarray(out.metadata['mapping'])
  # induced edges among {a, a+1, a+2}: a->a+1, a->a+2, a+1->a+2
  for p, a in ((0, 0), (1, 3)):
    got = set()
    for r, c, e, m in zip(row[p], col[p], edge[p], em[p]):
      if not m:
        continue
      u, v = int(node[p][r]), int(node[p][c])
      got.add((u, v))
      # edge ids: 0..N-1 are +1 edges, N..2N-1 are +2 edges
      assert (v == (u + 1) % N and e == u) or \
          (v == (u + 2) % N and e == N + u), (u, v, e)
    assert got == {(a, a + 1), (a, a + 2), (a + 1, a + 2)}
    # every seed maps to its position in the deduped node set
    for i, sd in enumerate(seeds[p]):
      assert node[p][mapping[p, i]] == sd


def test_dist_subgraph_with_expansion():
  num_parts = 2
  parts, _, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(dg, [2], mesh, seed=0)
  seeds = np.array([[0], [20]], np.int32)
  out = sampler.subgraph(seeds)
  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  em = np.asarray(out.edge_mask)
  for p, a in ((0, 0), (1, 20)):
    nn = int(np.asarray(out.num_nodes)[p])
    # 1-hop expansion of {a} with fanout 2 reaches {a, a+1, a+2}
    assert set(node[p][:nn].tolist()) == {a, a + 1, a + 2}
    got = {(int(node[p][r]), int(node[p][c]))
           for r, c, m in zip(row[p], col[p], em[p]) if m}
    assert got == {(a, a + 1), (a, a + 2), (a + 1, a + 2)}


def test_dist_weighted_sampling():
  """Edge-weight bias must survive the sharded engine (the reference GPU
  path falls back to uniform here — sampler/neighbor_sampler.py:86-91)."""
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  eids = np.arange(2 * N)
  w = np.concatenate([np.full(N, 1000.0),
                      np.full(N, 1e-3)]).astype(np.float32)
  pb = (np.arange(N) % 2).astype(np.int32)
  epb = pb[rows]
  parts = []
  for p in range(2):
    m = epb == p
    parts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]), eids=eids[m],
        weights=w[m]))
  mesh = make_mesh(2)
  dg = glt.distributed.DistGraph(2, 0, parts, pb, epb)
  sampler = glt.distributed.DistNeighborSampler(dg, [1], mesh, seed=0,
                                                with_weight=True)
  seeds = np.arange(N, dtype=np.int32).reshape(2, N // 2)
  n1 = n2 = 0
  for _ in range(10):
    out = sampler.sample_from_nodes(seeds)
    node = np.asarray(out.node)
    row = np.asarray(out.row)
    col = np.asarray(out.col)
    em = np.asarray(out.edge_mask)
    for p in range(2):
      for r, c, m in zip(row[p], col[p], em[p]):
        if not m:
          continue
        u, v = int(node[p][c]), int(node[p][r])
        if v == (u + 1) % N:
          n1 += 1
        else:
          assert v == (u + 2) % N
          n2 += 1
  assert n1 + n2 > 0
  assert n1 / (n1 + n2) > 0.95, (n1, n2)


def test_dist_link_loader_end_to_end():
  from graphlearn_tpu.sampler import NegativeSampling
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df)
  eli_seed = np.stack([np.arange(N), (np.arange(N) + 1) % N])
  loader = glt.distributed.DistLinkNeighborLoader(
      ds, [2, 2], eli_seed, batch_size=4, shuffle=True, seed=0,
      neg_sampling=NegativeSampling('binary', 1), mesh=mesh)
  steps = 0
  for batch in loader:
    steps += 1
    node = np.asarray(batch.node)
    x = np.asarray(batch.x)
    eli = np.asarray(batch.metadata['edge_label_index'])
    label = np.asarray(batch.metadata['edge_label'])
    assert label.shape == (num_parts, 8)
    for p in range(num_parts):
      nn = int(np.asarray(batch.num_nodes)[p])
      np.testing.assert_allclose(x[p, :nn, 0], node[p, :nn])
      # every positive pair is a +1 ring edge
      for i in range(4):
        u = int(node[p][eli[p, 0, i]])
        v = int(node[p][eli[p, 1, i]])
        assert v == (u + 1) % N
  assert steps == len(loader) == N // (num_parts * 4)


def test_dist_subgraph_loader_end_to_end():
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df)
  loader = glt.distributed.DistSubGraphLoader(
      ds, None, np.arange(N), batch_size=5, seed=0, mesh=mesh)
  steps = 0
  for batch in loader:
    steps += 1
    node = np.asarray(batch.node)
    x = np.asarray(batch.x)
    ei = np.asarray(batch.edge_index)
    em = np.asarray(batch.edge_mask)
    mapping = np.asarray(batch.metadata['mapping'])
    for p in range(num_parts):
      nn = int(np.asarray(batch.num_nodes)[p])
      np.testing.assert_allclose(x[p, :nn, 0], node[p, :nn])
      # all emitted edges are ring edges between batch nodes
      for r, c, m in zip(ei[p, 0], ei[p, 1], em[p]):
        if not m:
          continue
        u, v = int(node[p][r]), int(node[p][c])
        assert v in ((u + 1) % N, (u + 2) % N)
      assert (mapping[p] >= 0).all()
  assert steps == len(loader) == N // (num_parts * 5)


# ---------------------------------------------------------------- hetero

def hetero_ring_fixture(num_parts=2):
  """Two node types, two edge types, analytic books:
     ('u','to','v'):   u_i -> v_i and v_{(i+1)%N}
     ('v','back','u'): v_i -> u_{(i+2)%N}
     node_pb: u_i -> i%P, v_i -> (i+1)%P (different maps exercise routing).
  """
  et1, et2 = ('u', 'to', 'v'), ('v', 'back', 'u')
  r1 = np.concatenate([np.arange(N), np.arange(N)])
  c1 = np.concatenate([np.arange(N), (np.arange(N) + 1) % N])
  e1 = np.arange(2 * N)
  r2 = np.arange(N)
  c2 = (np.arange(N) + 2) % N
  e2 = np.arange(N)
  pb_u = (np.arange(N) % num_parts).astype(np.int32)
  pb_v = ((np.arange(N) + 1) % num_parts).astype(np.int32)
  parts = []
  for p in range(num_parts):
    part = {}
    m1 = pb_u[r1] == p      # et1 rows owned by u's partition
    part[et1] = GraphPartitionData(
        edge_index=np.stack([r1[m1], c1[m1]]), eids=e1[m1])
    m2 = pb_v[r2] == p      # et2 rows owned by v's partition
    part[et2] = GraphPartitionData(
        edge_index=np.stack([r2[m2], c2[m2]]), eids=e2[m2])
    parts.append(part)
  node_pb = {'u': pb_u, 'v': pb_v}
  feats = {
      'u': [(np.nonzero(pb_u == p)[0],
             np.nonzero(pb_u == p)[0][:, None].astype(np.float32) *
             np.ones((1, 4), np.float32)) for p in range(num_parts)],
      'v': [(np.nonzero(pb_v == p)[0],
             1000.0 + np.nonzero(pb_v == p)[0][:, None].astype(np.float32) *
             np.ones((1, 4), np.float32)) for p in range(num_parts)],
  }
  return parts, feats, node_pb, (et1, et2)


@pytest.mark.parametrize('num_parts', [2, 4])
def test_dist_hetero_sampler(num_parts):
  parts, feats, node_pb, (et1, et2) = hetero_ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)
  fanouts = {et1: [2, 2], et2: [1, 1]}
  sampler = glt.distributed.DistNeighborSampler(dg, fanouts, mesh, seed=0)
  seeds = np.arange(2 * num_parts, dtype=np.int32).reshape(num_parts, 2)
  out = sampler.sample_from_nodes(('u', seeds))

  rev1 = glt.typing.reverse_edge_type(et1)   # ('v', 'rev_to', 'u')
  rev2 = glt.typing.reverse_edge_type(et2)   # ('u', 'rev_back', 'v')
  assert set(out.row) == {rev1, rev2}
  node_u = np.asarray(out.node['u'])
  node_v = np.asarray(out.node['v'])
  for p in range(num_parts):
    # seeds lead u's node list
    assert set(node_u[p][:2].tolist()) == set(seeds[p].tolist())
    # et1 edges: neighbor v == u or u+1 (mod N), emitted under rev1
    r = np.asarray(out.row[rev1])[p]
    c = np.asarray(out.col[rev1])[p]
    m = np.asarray(out.edge_mask[rev1])[p]
    assert m.sum() > 0
    for ri, ci in zip(r[m], c[m]):
      u = int(node_u[p][ci]); v = int(node_v[p][ri])
      assert v in (u, (u + 1) % N), (u, v)
    # et2 edges: neighbor u == v+2 (mod N), emitted under rev2
    r = np.asarray(out.row[rev2])[p]
    c = np.asarray(out.col[rev2])[p]
    m = np.asarray(out.edge_mask[rev2])[p]
    assert m.sum() > 0
    for ri, ci in zip(r[m], c[m]):
      v = int(node_v[p][ci]); u = int(node_u[p][ri])
      assert u == (v + 2) % N, (v, u)
    # uniqueness per type
    for node, t in ((node_u, 'u'), (node_v, 'v')):
      nn = int(np.asarray(out.num_nodes[t])[p])
      valid = node[p][:nn]
      assert len(set(valid.tolist())) == nn


def test_dist_hetero_loader_end_to_end():
  num_parts = 2
  parts, feats, node_pb, (et1, et2) = hetero_ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)
  df = {t: glt.distributed.DistFeature(num_parts, feats[t], node_pb[t],
                                       mesh) for t in ('u', 'v')}
  labels = {'u': np.arange(N) % 5, 'v': np.arange(N) % 3}
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df,
                                   node_labels=labels)
  loader = glt.distributed.DistNeighborLoader(
      ds, {et1: [2, 2], et2: [1, 1]}, ('u', np.arange(N)), batch_size=4,
      shuffle=True, seed=0, mesh=mesh)
  steps = 0
  for batch in loader:
    steps += 1
    for t, base in (('u', 0.0), ('v', 1000.0)):
      node = np.asarray(batch.node[t])
      x = np.asarray(batch.x[t])
      y = np.asarray(batch.y[t])
      for p in range(num_parts):
        nn = int(np.asarray(batch.num_nodes[t])[p])
        np.testing.assert_allclose(x[p, :nn, 0], base + node[p, :nn])
        mod = 5 if t == 'u' else 3
        np.testing.assert_array_equal(y[p, :nn], node[p, :nn] % mod)
    assert set(batch.edge_index.keys()) == {
        glt.typing.reverse_edge_type(et1),
        glt.typing.reverse_edge_type(et2)}
  assert steps == len(loader) == N // (num_parts * 4)


def test_dist_tree_batches_support_dense_model():
  """The sharded engine's tree layout equals the local tree layout
  (same capacity plan, positional inducer, order-preserving exchange),
  so the dense-tree GraphSAGE forward is numerically identical to the
  segment-op forward on every shard of a dist tree batch."""
  import jax
  from graphlearn_tpu.models import train as train_lib
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df)
  loader = glt.distributed.DistNeighborLoader(
      ds, [2, 2], np.arange(N), batch_size=4, shuffle=False, seed=0,
      mesh=mesh, dedup='tree')
  batch = next(iter(loader))
  no, eo = train_lib.tree_hop_offsets(4, [2, 2])
  seg = glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2,
                             hop_node_offsets=no, hop_edge_offsets=eo)
  dense = glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2,
                               hop_node_offsets=no, hop_edge_offsets=eo,
                               tree_dense=True, fanouts=(2, 2))
  x = np.asarray(batch.x)
  ei = np.asarray(batch.edge_index)
  em = np.asarray(batch.edge_mask)
  params = seg.init(jax.random.PRNGKey(0), x[0], ei[0], em[0])
  for p in range(num_parts):
    o_seg = np.asarray(seg.apply(params, x[p], ei[p], em[p]))
    o_dense = np.asarray(dense.apply(params, x[p], ei[p], em[p]))
    nseed = int(np.asarray(batch.num_sampled_nodes)[p, 0])
    np.testing.assert_allclose(o_seg[:nseed], o_dense[:nseed],
                               rtol=2e-5, atol=2e-5)


def test_dist_hetero_tree_batches_support_hierarchical_model():
  """The typed sharded engine's tree layout equals hetero_tree_layout
  (same capacity plan), so the hierarchical RGNN forward matches the
  full forward on every shard of a dist hetero tree batch."""
  import jax
  num_parts = 2
  parts, feats, node_pb, (et1, et2) = hetero_ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)
  df = {t: glt.distributed.DistFeature(num_parts, feats[t], node_pb[t],
                                       mesh) for t in ('u', 'v')}
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df)
  fan = {et1: [2, 2], et2: [1, 1]}
  loader = glt.distributed.DistNeighborLoader(
      ds, fan, ('u', np.arange(N)), batch_size=4, shuffle=False, seed=0,
      mesh=mesh, dedup='tree')
  batch = next(iter(loader))
  no, eo = glt.sampler.hetero_tree_layout({'u': 4}, (et1, et2), fan)
  for t, v in batch.x.items():
    assert no[t][-1] == np.asarray(v).shape[1], (t, no[t])
  etypes = (glt.typing.reverse_edge_type(et1),
            glt.typing.reverse_edge_type(et2))
  full = glt.models.RGNN(etypes=etypes, hidden_dim=8, out_dim=3,
                         num_layers=2, out_ntype='u')
  hier = glt.models.RGNN(etypes=etypes, hidden_dim=8, out_dim=3,
                         num_layers=2, out_ntype='u',
                         hop_node_offsets=no, hop_edge_offsets=eo)
  def shard(d, p):
    return {k: np.asarray(v)[p] for k, v in d.items()}
  params = None
  for p in range(num_parts):
    x, ei, em = shard(batch.x, p), shard(batch.edge_index, p), \
        shard(batch.edge_mask, p)
    if params is None:
      params = full.init(jax.random.PRNGKey(0), x, ei, em)
    nseed = int(np.asarray(batch.num_sampled_nodes['u'])[p, 0])
    o_full = np.asarray(full.apply(params, x, ei, em))
    o_hier = np.asarray(hier.apply(params, x, ei, em))
    np.testing.assert_allclose(o_full[:nseed], o_hier[:nseed],
                               rtol=2e-5, atol=2e-5)


def test_dist_tree_with_node_budget():
  """dedup='tree' + node_budget in the sharded engine: buffers shrink to
  the budgeted layout and every emitted edge still decodes correctly."""
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(
      dg, [2, 2], mesh, seed=0, dedup='tree', node_budget=6)
  seeds = np.array([[0, 4, 8, 12], [1, 5, 9, 13]], np.int32)
  out = sampler.sample_from_nodes(seeds)
  node = np.asarray(out.node)
  from graphlearn_tpu.sampler.neighbor_sampler import (capacity_plan,
                                                       tree_layout_from_caps)
  no, _ = tree_layout_from_caps(capacity_plan(4, [2, 2], 6), [2, 2])
  assert node.shape == (num_parts, no[-1])
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  em = np.asarray(out.edge_mask)
  for p in range(num_parts):
    assert em[p].sum() > 0
    for r, c, m in zip(row[p], col[p], em[p]):
      if not m:
        continue
      u, v = int(node[p][c]), int(node[p][r])
      assert v in ((u + 1) % N, (u + 2) % N)


def test_dist_seed_labels_only():
  """seed_labels_only on the dist loader: y is the per-shard seed block
  only (homo), or the input type's seed block only (hetero)."""
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df,
                                   node_labels=np.arange(N) % 4)
  loader = glt.distributed.DistNeighborLoader(
      ds, [2, 2], np.arange(N), batch_size=4, shuffle=False, seed=0,
      mesh=mesh, seed_labels_only=True)
  batch = next(iter(loader))
  y = np.asarray(batch.y)
  node = np.asarray(batch.node)
  assert y.shape == (num_parts, 4)
  for p in range(num_parts):
    # the capped slots must BE the seed block (the invariant
    # seed_labels_only depends on), not just any aligned node ids
    np.testing.assert_array_equal(node[p, :4],
                                  np.arange(p * 4, (p + 1) * 4))
    np.testing.assert_array_equal(y[p], node[p, :4] % 4)


def test_dist_frontier_caps_sufficient_no_overflow():
  """Calibrated frontier_caps on the distributed engine: buffers shrink
  to the clamped plan, the sample stays structurally exact, and the
  replicated overflow flag is False when the caps suffice."""
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  # ring fanout [2, 2] from 4 seeds: hop1 <= 8 new, hop2 <= 8 new — caps
  # [8, 8] are sufficient yet clamp the worst-case [8, 16] plan
  sampler = glt.distributed.DistNeighborSampler(
      dg, [2, 2], mesh, seed=0, dedup='merge', frontier_caps=[8, 8])
  assert sampler.clamped_exact
  assert sampler.hop_caps(4) == [4, 8, 8]
  seeds = np.array([[0, 8, 16, 24], [1, 9, 17, 25]], np.int32)
  out = sampler.sample_from_nodes(seeds)
  node = np.asarray(out.node)
  assert node.shape == (num_parts, 4 + 8 + 8)   # clamped node buffer
  assert not np.any(np.asarray(out.metadata['overflow']))
  row, col = np.asarray(out.row), np.asarray(out.col)
  em = np.asarray(out.edge_mask)
  for p in range(num_parts):
    nn = int(np.asarray(out.num_nodes)[p])
    valid = node[p][:nn]
    assert len(set(valid.tolist())) == nn   # exact dedup
    assert em[p].sum() > 0
    for r, c, m in zip(row[p], col[p], em[p]):
      if not m:
        continue
      u, v = int(node[p][c]), int(node[p][r])
      assert v in ((u + 1) % N, (u + 2) % N)


def test_dist_frontier_caps_overflow_flag_and_policies():
  """Too-small caps: the replicated on-device flag trips; the loader's
  default policy raises at epoch end; 'recompute' replays offenders at
  full capacities with the SAME keys — byte-identical to an uncapped
  loader driven by the same seed."""
  import pytest
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  sampler = glt.distributed.DistNeighborSampler(
      dg, [2, 2], mesh, seed=0, dedup='merge', frontier_caps=[8, 2])
  seeds = np.array([[0, 8, 16, 24], [1, 9, 17, 25]], np.int32)
  out = sampler.sample_from_nodes(seeds)
  assert np.any(np.asarray(out.metadata['overflow']))

  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df,
                                   node_labels=np.arange(N) % 4)
  # stride-13 seed order keeps every batch's neighborhoods disjoint, so
  # hop 2 always exceeds cap 2 (consecutive seeds would overlap and fit)
  spread = (np.arange(N) * 13) % N
  # default policy: loud failure at epoch end
  loud = glt.distributed.DistNeighborLoader(
      ds, [2, 2], spread, batch_size=4, shuffle=False, seed=0,
      mesh=mesh, dedup='merge', frontier_caps=[8, 2])
  with pytest.raises(RuntimeError, match='frontier_caps overflowed'):
    for _ in loud:
      pass

  # 'recompute': every batch overflows -> every batch is replayed at
  # full caps with the same keys == the uncapped loader's output
  fix = glt.distributed.DistNeighborLoader(
      ds, [2, 2], spread, batch_size=4, shuffle=False, seed=0,
      mesh=mesh, dedup='merge', frontier_caps=[8, 2],
      overflow_policy='recompute')
  ref = glt.distributed.DistNeighborLoader(
      ds, [2, 2], spread, batch_size=4, shuffle=False, seed=0,
      mesh=mesh, dedup='merge', overflow_policy='off')
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


def test_dist_link_frontier_caps_overflow():
  """Calibrated caps on the distributed LINK engine: the engine derives
  the effective seed width itself; too-small caps trip the flag through
  sample_from_edges as well."""
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  rows = np.arange(8, dtype=np.int64) * 4
  cols = (rows + 1) % N
  sampler = glt.distributed.DistNeighborSampler(
      dg, [2], mesh, seed=0, dedup='merge', frontier_caps=[2])
  from graphlearn_tpu.sampler import EdgeSamplerInput
  out = sampler.sample_from_edges(
      EdgeSamplerInput(rows.reshape(2, 4), cols.reshape(2, 4)))
  assert np.any(np.asarray(out.metadata['overflow']))
  ok = glt.distributed.DistNeighborSampler(
      dg, [2], mesh, seed=0, dedup='merge', frontier_caps=[16])
  out2 = ok.sample_from_edges(
      EdgeSamplerInput(rows.reshape(2, 4), cols.reshape(2, 4)))
  assert not np.any(np.asarray(out2.metadata['overflow']))


def test_dist_hier_exchange_skewed_fallback_s4():
  """(slice=4, chip=2) mesh with a pathologically skewed partition book
  (every node owned by partition 0): the stage-2 DCN buckets — sized on
  the MEAN valid load — overflow on every hop, the psum'd replicated
  fallback takes the flat full-width path, and the sample is still
  loss-free: ring degree 2, fanout 2 keep-all => exactly 2 edges per
  seed."""
  import jax
  from jax.sharding import Mesh
  num_parts = 8
  if len(jax.devices()) < num_parts:
    pytest.skip('needs 8 devices')
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  eids = np.arange(2 * N)
  node_pb = np.zeros(N, np.int32)            # ALL nodes on partition 0
  parts = [GraphPartitionData(edge_index=np.stack([rows, cols]),
                              eids=eids)]
  for _ in range(num_parts - 1):
    parts.append(GraphPartitionData(edge_index=np.zeros((2, 0), np.int64),
                                    eids=np.zeros((0,), np.int64)))
  mesh = Mesh(np.array(jax.devices()[:num_parts]).reshape(4, 2),
              ('slice', 'chip'))
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb)
  sampler = glt.distributed.DistNeighborSampler(dg, [2, 2], mesh, seed=0,
                                                bucket_frac=0.5)
  b = 4
  seeds = np.arange(num_parts * b, dtype=np.int32).reshape(num_parts, b)
  out = sampler.sample_from_nodes(seeds)
  em = np.asarray(out.edge_mask)
  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  for p in range(num_parts):
    # hop 1 alone must contribute exactly 2 edges per seed (keep-all);
    # hop 2 adds more — the loss-free bound is >= 2*b
    assert int(em[p].sum()) >= 2 * b, int(em[p].sum())
    for r, c, m in zip(row[p], col[p], em[p]):
      if not m:
        continue
      u, v = int(node[p][c]), int(node[p][r])
      assert v in ((u + 1) % N, (u + 2) % N)
    nn = int(np.asarray(out.num_nodes)[p])
    assert len(set(node[p][:nn].tolist())) == nn


def worst_caps_from_plan(hop_caps):
  """{etype: [per-hop worst-case cap]} from an engine's own plan —
  caps at exactly the worst case make the clamped engine a structural
  no-op (shared by the node and link dist hetero caps tests)."""
  worst = {}
  for h, per in enumerate(hop_caps):
    for et, (fcap, k, cap) in per.items():
      assert cap == fcap * k
      worst.setdefault(et, [0] * len(hop_caps))[h] = cap
  return worst


def test_dist_hetero_calibrated_caps():
  """Dict-form calibrated caps on the DISTRIBUTED typed engine
  (round-5 parity with the local hetero clamps): caps at the plan's own
  worst case are byte-identical to the uncapped program (the max_new
  threading is a no-op at full width); tiny caps trip the REPLICATED
  on-device overflow flag; clamped results keep exact per-shard dedup;
  list caps on hetero graphs are rejected."""
  num_parts = 4
  parts, feats, node_pb, (et1, et2) = hetero_ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)
  fanouts = {et1: [2, 2], et2: [1, 1]}
  seeds = np.arange(2 * num_parts, dtype=np.int32).reshape(num_parts, 2)

  base = glt.distributed.DistNeighborSampler(dg, fanouts, mesh, seed=0,
                                             dedup='merge')
  _, hop_caps, _ = base._hetero_plan({'u': 2})
  worst = worst_caps_from_plan(hop_caps)
  capped = glt.distributed.DistNeighborSampler(
      dg, fanouts, mesh, seed=0, dedup='merge', frontier_caps=worst)
  o1 = base.sample_from_nodes(('u', seeds))
  o2 = capped.sample_from_nodes(('u', seeds))
  assert not bool(np.any(np.asarray(o2.metadata['overflow'])))
  for t in o1.node:
    np.testing.assert_array_equal(np.asarray(o1.node[t]),
                                  np.asarray(o2.node[t]))
  for et in o1.row:
    np.testing.assert_array_equal(np.asarray(o1.row[et]),
                                  np.asarray(o2.row[et]))
    np.testing.assert_array_equal(np.asarray(o1.edge_mask[et]),
                                  np.asarray(o2.edge_mask[et]))

  tiny = {et1: [1, 1], et2: [1, 1]}
  s_tiny = glt.distributed.DistNeighborSampler(
      dg, fanouts, mesh, seed=0, dedup='merge', frontier_caps=tiny)
  o3 = s_tiny.sample_from_nodes(('u', seeds))
  assert bool(np.any(np.asarray(o3.metadata['overflow'])))
  for t in o3.node:
    node = np.asarray(o3.node[t])
    nn = np.asarray(o3.num_nodes[t])
    for p in range(num_parts):
      valid = node[p][:int(nn[p])]
      assert len(set(valid.tolist())) == len(valid)

  with pytest.raises(ValueError, match='homogeneous-only'):
    glt.distributed.DistNeighborSampler(dg, fanouts, mesh, dedup='merge',
                                        frontier_caps=[4, 4])


@pytest.mark.slow   # tier-1 wall budget: hetero NODE calibrated caps +
def test_dist_hetero_link_calibrated_caps():   # homo link caps stay as reps
  """Distributed hetero LINK sampling under dict-form calibrated caps:
  the typed link plan (multi-type seed widths) threads the clamps;
  worst-case caps are byte-identical to uncapped; results carry the
  replicated overflow flag."""
  from graphlearn_tpu.sampler import EdgeSamplerInput, NegativeSampling
  num_parts = 2
  parts, _, node_pb, (et1, et2) = hetero_ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)
  fan = {et1: [2], et2: [1]}
  rows = np.array([[0, 4], [1, 5]], np.int32)
  cols = rows.copy()   # u_i -> v_i are real et1 edges
  inp = lambda: EdgeSamplerInput(
      rows, cols, input_type=et1,
      neg_sampling=NegativeSampling('binary', 1))

  base = glt.distributed.DistNeighborSampler(dg, fan, mesh, seed=0,
                                             dedup='merge')
  # the link plan seeds BOTH endpoint types (binary adds negatives):
  # take the worst-case caps from the engine's own plan
  o1 = base.sample_from_edges(inp())
  _, hop_caps, _ = base._hetero_plan(
      {'u': 2 + 2, 'v': 2 + 2})   # b + num_neg per endpoint type
  worst = worst_caps_from_plan(hop_caps)
  capped = glt.distributed.DistNeighborSampler(
      dg, fan, mesh, seed=0, dedup='merge', frontier_caps=worst)
  o2 = capped.sample_from_edges(inp())
  assert not bool(np.any(np.asarray(o2.metadata['overflow'])))
  for t in o1.node:
    np.testing.assert_array_equal(np.asarray(o1.node[t]),
                                  np.asarray(o2.node[t]))
  np.testing.assert_array_equal(
      np.asarray(o1.metadata['edge_label_index']),
      np.asarray(o2.metadata['edge_label_index']))

  tiny = {et1: [1], et2: [1]}
  s_tiny = glt.distributed.DistNeighborSampler(
      dg, fan, mesh, seed=0, dedup='merge', frontier_caps=tiny)
  o3 = s_tiny.sample_from_edges(inp())
  assert bool(np.any(np.asarray(o3.metadata['overflow'])))
  for t in o3.node:   # clamped results stay exact-dedup per shard
    node = np.asarray(o3.node[t])
    nn = np.asarray(o3.num_nodes[t])
    for p in range(num_parts):
      valid = node[p][:int(nn[p])]
      assert len(set(valid.tolist())) == len(valid)
