"""Hot-vertex cache + miss-only bucketed distributed feature exchange.

Contracts (ISSUE 3): the cached DistFeature lookup is BIT-EXACT against
the uncached full-width posture on every config (split ratios incl. 0
and 1, homo + hetero, flat + hierarchical meshes, skewed forced-fallback
requests), in-batch dedup fans one response row back to every slot that
asked for the id, the on-device hit/miss/overflow counters report hit
rates without per-batch host syncs, and ``get`` stays ONE instrumented
dispatch.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.typing import GraphPartitionData

from test_distributed import (N, hetero_ring_fixture, make_mesh,
                              ring_fixture)


def _uncached(num_parts, feats, node_pb, mesh):
  """The pre-cache posture: no cache, no dedup, full-width buckets."""
  return glt.distributed.DistFeature(
      num_parts, feats, node_pb, mesh, split_ratio=0.0,
      bucket_frac=None, dedup=False)


def _req_block(num_parts, b=12, seed=0, with_fill=True):
  """[P, b] request blocks mixing local/remote ids, duplicates and
  FILL(-1) pads — the node-buffer shape collate feeds."""
  rng = np.random.default_rng(seed)
  ids = rng.integers(0, N, (num_parts, b)).astype(np.int32)
  ids[:, 3] = ids[:, 2]                      # in-block duplicate
  if with_fill:
    ids[:, -1] = -1                          # FILL pad slot
  return ids


@pytest.mark.parametrize('num_parts,split_ratio', [
    (2, 0.0), (2, 0.2), (2, 0.5), (2, 1.0), (4, 0.2)])  # tier-1 budget
def test_dist_feature_cache_bitexact(num_parts, split_ratio):
  """Cached vs uncached ``get`` is bit-exact at every split_ratio, with
  in-degree-style hotness scores and mixed hit/miss/pad requests."""
  _, feats, node_pb, _ = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  hotness = np.roll(np.arange(N), 7)         # arbitrary but fixed scores
  df = glt.distributed.DistFeature(
      num_parts, feats, node_pb, mesh, split_ratio=split_ratio,
      hotness=hotness)
  ref = _uncached(num_parts, feats, node_pb, mesh)
  ids = _req_block(num_parts)
  got = np.asarray(df.get(ids))
  want = np.asarray(ref.get(ids))
  np.testing.assert_array_equal(got, want)
  # and against the analytic values
  np.testing.assert_allclose(
      got[..., 0], np.where(ids >= 0, ids, 0).astype(np.float32))
  s = df.stats()
  assert s['lookups'] == int((ids >= 0).sum())
  assert s['hits'] + s['misses'] == s['lookups']
  assert s['overflow'] == 0
  if split_ratio == 0.0:
    assert s['hits'] == 0
  if split_ratio == 1.0:
    assert s['misses'] == 0


def test_dist_feature_cache_rows_override():
  """``cache_rows`` overrides split_ratio (the local Feature knob pair)
  and hotness=None caches the lowest ids (hot-first layouts)."""
  num_parts = 2
  _, feats, node_pb, _ = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh,
                                   split_ratio=0.9, cache_rows=4)
  assert df.cache_rows == 4
  np.testing.assert_array_equal(df.cache_ids, np.arange(4))
  ids = np.array([[0, 1, 2, 3], [4, 5, 6, 7]], np.int32)
  out = np.asarray(df.get(ids))
  np.testing.assert_allclose(out[..., 0], ids.astype(np.float32))
  s = df.stats()
  assert s['hits'] == 4 and s['misses'] == 4


def test_dist_feature_dedup_one_id_many_slots():
  """One missed id filling most batch slots collapses to ONE wire
  request whose response fans back to every slot."""
  num_parts = 2
  _, feats, node_pb, _ = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh)
  b = 16
  ids = np.full((num_parts, b), 9, np.int32)   # 9 is remote to shard 0
  ids[1, :] = 22
  ids[:, -1] = -1
  out = np.asarray(df.get(ids))
  np.testing.assert_allclose(
      out[..., 0], np.where(ids >= 0, ids, 0).astype(np.float32))
  s = df.stats()
  assert s['misses'] == 2 * (b - 1)
  assert s['unique_misses'] == 2                # one per shard
  assert s['overflow'] == 0


@pytest.mark.parametrize('split_ratio', [0.0, 0.25])
def test_dist_feature_skewed_forced_fallback(split_ratio):
  """Pathologically skewed ownership (every id on partition 0) with a
  tiny bucket_frac: the fractional buckets overflow, the psum'd
  replicated lax.cond takes the full-width path, and the lookup is
  still bit-exact (the sampler-exchange loss-free contract, pinned like
  test_dist_hier_exchange_skewed_fallback_s4)."""
  num_parts = 4
  mesh = make_mesh(num_parts)
  pb0 = np.zeros(N, np.int32)
  feats = [(np.arange(N, dtype=np.int64),
            np.arange(N, dtype=np.float32)[:, None] *
            np.ones((1, 4), np.float32))]
  feats += [(np.zeros(0, np.int64), np.zeros((0, 4), np.float32))
            for _ in range(num_parts - 1)]
  df = glt.distributed.DistFeature(
      num_parts, feats, pb0, mesh, split_ratio=split_ratio,
      bucket_frac=0.5)
  ids = _req_block(num_parts, b=16, seed=3)
  out = np.asarray(df.get(ids))
  np.testing.assert_allclose(
      out[..., 0], np.where(ids >= 0, ids, 0).astype(np.float32))
  s = df.stats()
  if split_ratio == 0.0:
    assert s['overflow'] > 0, 'skew must exercise the fallback'


def test_dist_feature_hier_mesh_cached_and_fallback():
  """(slice=4, chip=2) mesh: the hierarchical 2-stage miss exchange is
  bit-exact vs the uncached flat-full-width posture, and the skewed
  book forces the stage-2 DCN overflow fallback, still exact."""
  import jax
  from jax.sharding import Mesh
  num_parts = 8
  if len(jax.devices()) < num_parts:
    pytest.skip('needs 8 devices')
  mesh = Mesh(np.array(jax.devices()[:num_parts]).reshape(4, 2),
              ('slice', 'chip'))
  node_pb = (np.arange(N) % num_parts).astype(np.int32)
  feats = []
  for p in range(num_parts):
    owned = np.nonzero(node_pb == p)[0]
    feats.append((owned.astype(np.int64),
                  owned[:, None].astype(np.float32) *
                  np.ones((1, 4), np.float32)))
  ids = _req_block(num_parts, b=16, seed=5)
  ref = _uncached(num_parts, feats, node_pb, mesh)
  want = np.asarray(ref.get(ids))
  for split_ratio in (0.0, 0.25, 1.0):
    df = glt.distributed.DistFeature(
        num_parts, feats, node_pb, mesh, split_ratio=split_ratio,
        hotness=np.arange(N)[::-1].copy())
    np.testing.assert_array_equal(np.asarray(df.get(ids)), want)
    assert df.stats()['overflow'] == 0
  # skewed book -> stage-2 overflow -> replicated flat fallback
  pb0 = np.zeros(N, np.int32)
  f0 = [(np.arange(N, dtype=np.int64),
         np.arange(N, dtype=np.float32)[:, None] *
         np.ones((1, 4), np.float32))]
  f0 += [(np.zeros(0, np.int64), np.zeros((0, 4), np.float32))
         for _ in range(num_parts - 1)]
  dfs = glt.distributed.DistFeature(num_parts, f0, pb0, mesh,
                                    bucket_frac=0.5)
  out = np.asarray(dfs.get(ids))
  np.testing.assert_allclose(
      out[..., 0], np.where(ids >= 0, ids, 0).astype(np.float32))
  assert dfs.stats()['overflow'] > 0


def test_dist_feature_wire_dtype():
  """bf16 wire rows halve response bytes; values match f32 within bf16
  tolerance, and a bf16 STORAGE store is bit-exact through the bf16
  wire (the cast is a no-op then)."""
  import jax.numpy as jnp
  num_parts = 2
  _, feats, node_pb, _ = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  ids = _req_block(num_parts)
  ref = _uncached(num_parts, feats, node_pb, mesh)
  want = np.asarray(ref.get(ids))
  dfw = glt.distributed.DistFeature(
      num_parts, feats, node_pb, mesh, split_ratio=0.25,
      wire_dtype=jnp.bfloat16)
  got = np.asarray(dfw.get(ids))
  assert got.dtype == np.float32        # storage dtype preserved
  np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)
  # bf16 storage: wire cast is identity -> bit-exact vs bf16 reference
  ref16 = glt.distributed.DistFeature(
      num_parts, feats, node_pb, mesh, dtype=jnp.bfloat16,
      bucket_frac=None, dedup=False)
  df16 = glt.distributed.DistFeature(
      num_parts, feats, node_pb, mesh, dtype=jnp.bfloat16,
      split_ratio=0.25, wire_dtype=jnp.bfloat16)
  np.testing.assert_array_equal(
      np.asarray(df16.get(ids)).astype(np.float32),
      np.asarray(ref16.get(ids)).astype(np.float32))


def test_dist_feature_hetero_cached_loader_end_to_end():
  """Hetero: per-type cached stores through DistNeighborLoader produce
  byte-identical batch features vs uncached stores."""
  num_parts = 2
  parts, feats, node_pb, (et1, et2) = hetero_ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)

  def run(split_ratio):
    df = {t: glt.distributed.DistFeature(
        num_parts, feats[t], node_pb[t], mesh, split_ratio=split_ratio,
        hotness=np.arange(N)[::-1].copy()) for t in ('u', 'v')}
    ds = glt.distributed.DistDataset(num_parts, 0, dg, df)
    loader = glt.distributed.DistNeighborLoader(
        ds, {et1: [2, 2], et2: [1, 1]}, ('u', np.arange(N)),
        batch_size=4, shuffle=False, seed=0, mesh=mesh)
    return [{t: np.asarray(b.x[t]) for t in b.x} for b in loader]

  base = run(0.0)
  cached = run(0.5)
  assert len(base) == len(cached) > 0
  for b0, b1 in zip(base, cached):
    assert set(b0) == set(b1)
    for t in b0:
      np.testing.assert_array_equal(b0[t], b1[t])


def test_dist_feature_one_dispatch_no_host_sync():
  """CI guard: the hot-loop ``get`` is ONE instrumented dispatch and
  keeps its counters on device — no device->host fetch until stats()
  is called explicitly (per epoch)."""
  import jax
  num_parts = 2
  _, feats, node_pb, _ = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh,
                                   split_ratio=0.25)
  ids = _req_block(num_parts)
  df.get(ids)                                    # compile outside count
  steps = 5
  with glt.utils.count_dispatches() as dc:
    outs = [df.get(ids) for _ in range(steps)]
  jax.block_until_ready(outs)
  assert dc.counts == {'dist_feature.get': steps}, dc.counts
  assert dc.total == steps
  # the accumulator stays a device array between batches (fetching it
  # per batch would sync the host to the device); only stats() reads
  assert isinstance(df._stats, jax.Array)
  s = df.stats()
  assert s['lookups'] == (steps + 1) * int((ids >= 0).sum())
  # wrap_dispatch interop: external call sites can layer their own label
  wrapped = glt.utils.wrap_dispatch(df.get, 'bench.feature_get')
  with glt.utils.count_dispatches() as dc2:
    wrapped(ids)
  assert dc2.counts == {'bench.feature_get': 1, 'dist_feature.get': 1}


def test_dist_feature_stats_publish_and_loader_epoch():
  """publish_stats lands the epoch's counters in utils.trace and
  resets; DistLoader publishes once per epoch."""
  from graphlearn_tpu.utils import trace
  num_parts = 2
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh,
                                   split_ratio=0.25)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df)
  loader = glt.distributed.DistNeighborLoader(
      ds, [2, 2], np.arange(N), batch_size=4, shuffle=False, seed=0,
      mesh=mesh)
  trace.reset_counters('dist_feature.')
  steps = sum(1 for _ in loader)
  assert steps > 0
  c = trace.counters('dist_feature.')
  assert c.get('dist_feature.lookups', 0) > 0
  assert c.get('dist_feature.hits', 0) > 0
  # published counters were reset out of the device accumulator
  assert df.stats()['lookups'] == 0
  trace.reset_counters('dist_feature.')


def test_dist_dataset_load_with_cache(tmp_path):
  """DistDataset.load plumbs split_ratio/hotness into the node feature
  store; batches stay byte-identical to the uncached load."""
  from graphlearn_tpu.distributed.dist_dataset import DistDataset
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  feat = np.arange(N, dtype=np.float32)[:, None] * np.ones((1, 4),
                                                           np.float32)
  glt.partition.RandomPartitioner(
      str(tmp_path), 2, N, np.stack([rows, cols]), node_feat=feat,
      seed=0).partition()
  mesh = make_mesh(2)
  ds0 = DistDataset().load(str(tmp_path), mesh=mesh)
  ds1 = DistDataset().load(str(tmp_path), mesh=mesh, split_ratio=0.3)
  assert ds1.node_features.cache_rows == int(N * 0.3)
  ids = _req_block(2)
  np.testing.assert_array_equal(np.asarray(ds1.node_features.get(ids)),
                                np.asarray(ds0.node_features.get(ids)))
  assert ds1.node_features.stats()['hits'] > 0


def test_feature_exchange_mb_accounting():
  """The analytic volume helper the benchmarks report: full-width
  posture = P x width x (id + F x 4B); the miss-only posture at the
  products config (P=8, split_ratio=0.2, bf16 wire) is >= 2x smaller
  (the dryrun acceptance bar)."""
  from graphlearn_tpu.distributed.dist_feature import (
      feature_exchange_mb, miss_capacity)
  w, p, f = 1024, 8, 100
  full = feature_exchange_mb(w, p, f, bucket_frac=None, wire_bytes=4)
  assert full == p * w * (4 + f * 4) / 1e6
  opt = feature_exchange_mb(w, p, f, bucket_frac=2.0, wire_bytes=2,
                            hit_rate=0.2)
  assert full / opt >= 2.0
  # capacity: frac x mean miss load, lane-rounded, clamped loss-free
  assert miss_capacity(w, p, 2.0, 0.2) == \
      min(w, max(8, -(-int(2.0 * int(np.ceil(w * 0.8)) / p) // 8) * 8))
  assert miss_capacity(w, p, None) == w
  assert miss_capacity(w, 1, 2.0) == w
