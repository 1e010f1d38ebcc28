"""DistScanTrainer: scanned distributed epochs on the virtual CPU mesh.

The scanned distributed epoch must be a pure EXECUTION change over the
per-step collocated loop: with shuffle=False the on-device seed matrix
replays DistLoader._index_blocks exactly (arange order, cyclic tail
padding, validity mask) and the in-scan fold_in key replay matches
DistNeighborSampler._keys_for's counter discipline, so per-step losses
and final params are BIT-IDENTICAL — including a ragged tail batch and a
tail chunk. The dispatch counter then pins the subsystem's point: one
epoch issues <= ceil(steps/K) + 2 instrumented dispatches where the
per-step loop pays >= 2 per step (sample + collate + feature/label
gathers + train step), and the feature-cache epoch stats survive the
scan carry unchanged (publish parity, zero per-batch host syncs).
"""
import gc

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.models import train as train_lib
from graphlearn_tpu.typing import GraphPartitionData

N = 40


def ring_fixture(num_parts):
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


def make_mesh(num_parts, shape=None):
  import jax
  from jax.sharding import Mesh
  devs = np.array(jax.devices()[:num_parts])
  if shape is not None:
    return Mesh(devs.reshape(shape), ('slice', 'chip'))
  return Mesh(devs, ('g',))


def make_homo_loader(num_parts, num_seeds, mesh=None, batch_size=2,
                     split_ratio=0.25, **kw):
  parts, feats, node_pb, edge_pb = ring_fixture(num_parts)
  if mesh is None:
    mesh = make_mesh(num_parts)
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh,
                                   split_ratio=split_ratio)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df,
                                   node_labels=np.arange(N) % 3)
  kw.setdefault('shuffle', False)
  kw.setdefault('drop_last', False)
  return glt.distributed.DistNeighborLoader(
      ds, [2, 2], np.arange(num_seeds), batch_size=batch_size, seed=0,
      mesh=mesh, **kw)


def init_state(model, loader, tx):
  """Template-batch init; counters are polluted by the template epoch's
  GC'd publish, so callers reset after this."""
  import jax
  import jax.numpy as jnp
  first = next(iter(loader))
  if isinstance(first.x, dict):
    one = lambda d: {k: np.asarray(v)[0] for k, v in d.items()}
    params = model.init(jax.random.PRNGKey(0), one(first.x),
                        one(first.edge_index), one(first.edge_mask))
  else:
    params = model.init(jax.random.PRNGKey(0), np.asarray(first.x)[0],
                        np.asarray(first.edge_index)[0],
                        np.asarray(first.edge_mask)[0])
  return train_lib.TrainState(params, tx.init(params), jnp.int32(0))


def fresh_counters():
  """Drop any feature-stats publish a GC'd template iterator left."""
  gc.collect()
  glt.utils.trace.reset_counters('dist_feature')


def run_equivalence(make_loader, model, tx, steps, chunk,
                    num_classes=3):
  """Shared bit-exactness protocol: per-step reference epoch vs scanned
  epoch from identical fresh loaders/state, two epochs (stream
  continuation), published feature-stats parity, dispatch budgets."""
  import jax
  ref_loader = make_loader()
  ref = glt.loader.DistFusedEpochTrainer(ref_loader, model, tx,
                                         num_classes)
  state_ref = init_state(model, make_loader(), tx)
  scan_loader = make_loader()
  trainer = glt.loader.DistScanTrainer(scan_loader, model, tx,
                                       num_classes, chunk_size=chunk)
  state_scan = init_state(model, make_loader(), tx)

  fresh_counters()
  with glt.utils.count_dispatches() as dc_step:
    state_ref, losses_ref = ref.run_epoch_steps(state_ref)
  losses_ref = np.asarray([np.asarray(x) for x in losses_ref])
  stats_ref = glt.utils.trace.counters('dist_feature')
  assert len(losses_ref) == steps == len(ref_loader)
  # dispatch budget: the per-step loop pays >= 2 instrumented program
  # launches per batch on the distributed hot path alone
  assert dc_step.subtotal('dist_') >= 2 * steps, dc_step
  assert dc_step.counts['dist_sample'] == steps
  assert dc_step.counts['dist_collate'] == steps

  fresh_counters()
  from graphlearn_tpu.metrics import programs
  c0 = programs.compile_count('dist_scan_chunk')
  with glt.utils.count_dispatches() as dc_scan:
    state_scan, losses, accs = trainer.run_epoch(state_scan)
  losses = np.asarray(losses)
  stats_scan = glt.utils.trace.counters('dist_feature')

  # the scan's whole-epoch budget: ceil(steps/K) + 2
  assert dc_scan.total <= -(-steps // chunk) + 2, dc_scan
  assert dc_scan.counts['dist_scan_chunk'] == -(-steps // chunk)
  # program observatory (GLT_STRICT): compile_count == the executable
  # population — ONE per chunk LENGTH (full K + optional tail), zero
  # extra dispatches (dc_scan above bit-matches with it armed)
  n_lengths = 1 if (steps <= chunk or steps % chunk == 0) else 2
  assert programs.compile_count('dist_scan_chunk') - c0 == n_lengths
  # bit-exact losses + params
  np.testing.assert_array_equal(losses, losses_ref)
  assert np.asarray(accs).shape == (steps,)
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state_scan.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # feature-cache epoch stats survive the scan carry: the scanned epoch
  # publishes the SAME dist_feature.* counters as the per-step loop
  assert stats_ref == stats_scan and stats_ref, (stats_ref, stats_scan)
  # the host fold_in stream advanced identically: a SECOND epoch of
  # both runs still matches (stream continuation)
  assert scan_loader.sampler._call_count == ref_loader.sampler._call_count
  state_ref, losses_ref2 = ref.run_epoch_steps(state_ref)
  with programs.retrace_budget('dist_scan_chunk', 0):   # steady state
    state_scan, losses2, _ = trainer.run_epoch(state_scan)
  assert programs.compile_count('dist_scan_chunk') - c0 == n_lengths
  np.testing.assert_array_equal(
      np.asarray(losses2),
      np.asarray([np.asarray(x) for x in losses_ref2]))
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state_scan.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dist_scan_matches_per_step_homo_8dev():
  """8-device flat mesh (the acceptance bar): scanned epoch ==
  per-step collocated loop bit-exactly, with a ragged tail batch
  (38 seeds / global batch 16 -> 2 full + 1 masked tail) and a tail
  chunk (3 steps at K=2 -> chunks of 2 and 1)."""
  import jax
  if len(jax.devices()) < 8:
    pytest.skip('needs 8 devices')
  import optax
  model = glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  tx = optax.adam(1e-2)
  run_equivalence(lambda: make_homo_loader(8, 38), model, tx, steps=3,
                  chunk=2)


def test_dist_scan_matches_per_step_hetero():
  """Typed engine equivalence on a 2-partition mesh: the scanned chunk
  inlines _hetero_engine + per-ntype cached feature lookups (one stats
  row per store in the carry) + the seed type's label gather."""
  import optax
  num_parts = 2
  et1, et2 = ('u', 'to', 'v'), ('v', 'back', 'u')

  def hetero_fixture():
    r1 = np.concatenate([np.arange(N), np.arange(N)])
    c1 = np.concatenate([np.arange(N), (np.arange(N) + 1) % N])
    r2 = np.arange(N)
    c2 = (np.arange(N) + 2) % N
    pb_u = (np.arange(N) % num_parts).astype(np.int32)
    pb_v = ((np.arange(N) + 1) % num_parts).astype(np.int32)
    parts = []
    for p in range(num_parts):
      part = {}
      m1 = pb_u[r1] == p
      part[et1] = GraphPartitionData(
          edge_index=np.stack([r1[m1], c1[m1]]),
          eids=np.arange(2 * N)[m1])
      m2 = pb_v[r2] == p
      part[et2] = GraphPartitionData(
          edge_index=np.stack([r2[m2], c2[m2]]),
          eids=np.arange(N)[m2])
      parts.append(part)
    node_pb = {'u': pb_u, 'v': pb_v}
    feats = {t: [(np.nonzero(node_pb[t] == p)[0],
                  np.nonzero(node_pb[t] == p)[0][:, None].astype(
                      np.float32) * np.ones((1, 4), np.float32))
                 for p in range(num_parts)] for t in ('u', 'v')}
    return parts, feats, node_pb

  def make_loader():
    parts, feats, node_pb = hetero_fixture()
    mesh = make_mesh(num_parts)
    dg = glt.distributed.DistHeteroGraph(num_parts, 0, parts, node_pb)
    df = {t: glt.distributed.DistFeature(num_parts, feats[t],
                                         node_pb[t], mesh,
                                         split_ratio=0.25)
          for t in ('u', 'v')}
    ds = glt.distributed.DistDataset(
        num_parts, 0, dg, df,
        node_labels={'u': np.arange(N) % 3, 'v': np.arange(N) % 3})
    # 14 seeds, global batch 4 -> 3 full + 1 ragged tail = 4 steps
    return glt.distributed.DistNeighborLoader(
        ds, {et1: [2, 2], et2: [1, 1]}, ('u', np.arange(14)),
        batch_size=2, shuffle=False, drop_last=False, seed=0, mesh=mesh)

  etypes = (glt.typing.reverse_edge_type(et1),
            glt.typing.reverse_edge_type(et2))
  model = glt.models.RGNN(etypes=etypes, hidden_dim=8, out_dim=3,
                          num_layers=2, out_ntype='u')
  tx = optax.adam(1e-2)
  # chunk=4 = one full-epoch chunk: the tail-CHUNK retrace is covered
  # by the homo test; one typed chunk compile keeps this inside the
  # tier-1 wall budget (conftest canary)
  run_equivalence(make_loader, model, tx, steps=4, chunk=4)


def test_dist_scan_device_shuffle_covers_epoch():
  """shuffle=True scanned epochs draw the permutation ON DEVICE: the
  seed matrix covers every seed exactly once per epoch, tail pads are
  cyclic-masked, and consecutive epochs permute differently."""
  import jax
  import optax
  loader = make_homo_loader(2, 20, shuffle=True)   # 5 steps of 4
  model = glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  trainer = glt.loader.DistScanTrainer(loader, model, optax.adam(1e-2),
                                       3, chunk_size=2)
  seeds_dev = jax.numpy.asarray(np.arange(20, dtype=np.int32))
  k0 = jax.random.fold_in(trainer._perm_key, 0)
  seed_mat, mask_mat = trainer._seed_fn(seeds_dev, k0, 5)
  assert seed_mat.shape == (2, 5, 2) and bool(np.asarray(mask_mat).all())
  assert sorted(np.asarray(seed_mat).reshape(-1).tolist()) == \
      list(range(20))
  seed_mat2, _ = trainer._seed_fn(seeds_dev,
                                  jax.random.fold_in(trainer._perm_key, 1),
                                  5)
  assert not np.array_equal(np.asarray(seed_mat), np.asarray(seed_mat2))
  # ragged tail: the pad slots cycle the epoch order and are masked
  seed_mat3, mask3 = trainer._seed_fn(seeds_dev, k0, 6)
  m = np.asarray(mask3)
  assert m.sum() == 20 and m.size == 24


def test_dist_scan_rejects_remote_and_recompute():
  """Clear errors at construction: scanned epochs are collocated-mesh
  only (remote/mp loaders keep the per-step loop — their failover acks
  need per-batch host visibility, docs/failure_model.md), and
  overflow_policy='recompute' needs a per-batch host sync."""
  import optax
  model = glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  tx = optax.adam(1e-2)

  class FakeRemoteLoader:   # stands in for Remote/MpDistNeighborLoader
    pass

  with pytest.raises(ValueError, match='collocated'):
    glt.loader.DistScanTrainer(FakeRemoteLoader(), model, tx, 3)

  loader = make_homo_loader(2, 16, dedup='merge', frontier_caps=[8, 8],
                            overflow_policy='recompute')
  with pytest.raises(ValueError, match='recompute'):
    glt.loader.DistScanTrainer(loader, model, tx, 3)

  # link loaders keep the per-step loop too
  parts, feats, node_pb, edge_pb = ring_fixture(2)
  mesh = make_mesh(2)
  dg = glt.distributed.DistGraph(2, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(2, feats, node_pb, mesh)
  ds = glt.distributed.DistDataset(2, 0, dg, df,
                                   node_labels=np.arange(N) % 3)
  link = glt.distributed.DistLinkNeighborLoader(
      ds, [2], np.stack([np.arange(8), (np.arange(8) + 1) % N]),
      batch_size=2, mesh=mesh)
  with pytest.raises(ValueError, match='NODE'):
    glt.loader.DistScanTrainer(link, model, tx, 3)

  with pytest.raises(ValueError, match='chunk_size'):
    glt.loader.DistScanTrainer(make_homo_loader(2, 16), model, tx, 3,
                               chunk_size=0)


def test_dist_scan_overflow_guard():
  """Calibrated-caps overflow rides the scan carry psum-replicated:
  'raise' fires at epoch end with zero in-epoch syncs; a max_steps
  break defers to check_overflow()."""
  import optax
  model = glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  tx = optax.adam(1e-2)
  # stride-13 seed order keeps neighborhoods disjoint so hop 2 always
  # exceeds cap 2 (the loud-loader protocol of test_distributed)
  spread = (np.arange(16) * 13) % N

  def mk(**kw):
    parts, feats, node_pb, edge_pb = ring_fixture(2)
    mesh = make_mesh(2)
    dg = glt.distributed.DistGraph(2, 0, parts, node_pb, edge_pb)
    df = glt.distributed.DistFeature(2, feats, node_pb, mesh)
    ds = glt.distributed.DistDataset(2, 0, dg, df,
                                     node_labels=np.arange(N) % 3)
    return glt.distributed.DistNeighborLoader(
        ds, [2, 2], spread, batch_size=2, shuffle=False, seed=0,
        mesh=mesh, dedup='merge', **kw)

  loader = mk(frontier_caps=[8, 2])
  trainer = glt.loader.DistScanTrainer(loader, model, tx, 3,
                                       chunk_size=2)
  state = init_state(model, mk(frontier_caps=[8, 2],
                               overflow_policy='off'), tx)
  with pytest.raises(RuntimeError, match='frontier_caps overflowed'):
    trainer.run_epoch(state)

  loader2 = mk(frontier_caps=[8, 2])
  trainer2 = glt.loader.DistScanTrainer(loader2, model, tx, 3,
                                        chunk_size=2)
  state = init_state(model, mk(frontier_caps=[8, 2],
                               overflow_policy='off'), tx)
  state, _, _ = trainer2.run_epoch(state, max_steps=2)
  assert loader2.check_overflow()


@pytest.mark.slow  # tier-1 budget: 8-device hierarchical-mesh compile
def test_dist_scan_matches_per_step_hier_mesh():
  """2-axis (slice=2, chip=4) mesh: the scanned chunk composes the
  HIERARCHICAL exchanges (sampler + feature store) and still replays
  the per-step loop bit-exactly."""
  import jax
  import optax
  if len(jax.devices()) < 8:
    pytest.skip('needs 8 devices')
  mk = lambda: make_homo_loader(8, 38, mesh=make_mesh(8, shape=(2, 4)))
  model = glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  run_equivalence(mk, model, optax.adam(1e-2), steps=3, chunk=2)


def test_dist_sampler_fold_in_counter_state():
  """The distributed sampler's fold_in counter discipline: state_dict
  round-trips the stream position; replaying a count gives bit-identical
  keys (the scanned epoch's replay contract); pre-counter checkpoints
  (bare 'key') load at position 0."""
  parts, _, node_pb, edge_pb = ring_fixture(2)
  mesh = make_mesh(2)
  dg = glt.distributed.DistGraph(2, 0, parts, node_pb, edge_pb)
  s = glt.distributed.DistNeighborSampler(dg, [2], mesh, seed=7)
  k1 = np.asarray(s._next_keys())
  k2 = np.asarray(s._next_keys())
  assert s._call_count == 2
  assert not np.array_equal(k1, k2)
  np.testing.assert_array_equal(np.asarray(s._keys_for(1)), k1)
  st = s.state_dict()
  s2 = glt.distributed.DistNeighborSampler(dg, [2], mesh, seed=0)
  s2.load_state_dict(st)
  np.testing.assert_array_equal(np.asarray(s2._next_keys()),
                                np.asarray(s._next_keys()))
  s3 = glt.distributed.DistNeighborSampler(dg, [2], mesh, seed=7)
  s3.load_state_dict({'key': st['key']})   # legacy checkpoint
  np.testing.assert_array_equal(np.asarray(s3._next_keys()), k1)
