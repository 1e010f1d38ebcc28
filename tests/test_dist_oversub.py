"""Device oversubscription through the distributed shard exchange.

``storage.TieredDistScanTrainer`` must be a pure EXECUTION change over
the all-HBM ``DistScanTrainer``: each shard's HBM holds only its hot
prefix + the chunk's staged exchange slab, the epoch prologue's
id-only sampler replay computes the exact per-chunk miss-exchange
program, and the in-program slab-backed lookup
(``DistFeature._shard_body(slab=True)``) returns byte-identical rows —
so losses AND params are BIT-IDENTICAL at >= 4x per-shard feature
oversubscription, at the unchanged ceil(steps/K)+2 dispatch budget
under GLT_STRICT (conftest arms it for this module). The chaos test
pins the failure contract: an armed ``storage.dist_stage`` fault
degrades every slab to a synchronous gather of the same planned
positions — bit-identical, never wrong (docs/failure_model.md).
"""
import gc
import tempfile

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu import metrics as glt_metrics
from graphlearn_tpu.models import train as train_lib
from graphlearn_tpu.storage import TieredDistFeature, TieredDistScanTrainer
from graphlearn_tpu.typing import GraphPartitionData
from graphlearn_tpu.utils import faults

N = 40
NUM_PARTS = 2
HOT_PREFIX = 4   # of 20 rows/shard: 5x per-shard oversubscription


def ring_fixture():
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  eids = np.arange(2 * N)
  node_pb = (np.arange(N) % NUM_PARTS).astype(np.int32)
  edge_pb = node_pb[rows]
  parts, feats = [], []
  for p in range(NUM_PARTS):
    m = edge_pb == p
    parts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]), eids=eids[m]))
    ids = np.nonzero(node_pb == p)[0]
    feats.append((ids.astype(np.int64),
                  ids[:, None].astype(np.float32) * np.ones((1, 4),
                                                            np.float32)))
  return parts, feats, node_pb, edge_pb


def make_mesh():
  import jax
  from jax.sharding import Mesh
  return Mesh(np.array(jax.devices()[:NUM_PARTS]), ('g',))


def make_loader(tiered, spill_dir=None, num_seeds=38, shuffle=False,
                split_ratio=0.25, hot_prefix=HOT_PREFIX):
  parts, feats, node_pb, edge_pb = ring_fixture()
  mesh = make_mesh()
  dg = glt.distributed.DistGraph(NUM_PARTS, 0, parts, node_pb, edge_pb)
  if tiered:
    df = TieredDistFeature(NUM_PARTS, feats, node_pb, mesh=mesh,
                           spill_dir=spill_dir,
                           hot_prefix_rows=hot_prefix,
                           split_ratio=split_ratio)
  else:
    df = glt.distributed.DistFeature(NUM_PARTS, feats, node_pb, mesh,
                                     split_ratio=split_ratio)
  ds = glt.distributed.DistDataset(NUM_PARTS, 0, dg, df,
                                   node_labels=np.arange(N) % 3)
  return glt.distributed.DistNeighborLoader(
      ds, [2, 2], np.arange(num_seeds), batch_size=2, seed=0, mesh=mesh,
      shuffle=shuffle, drop_last=False)


def init_state(model, loader, tx):
  import jax
  import jax.numpy as jnp
  first = next(iter(loader))
  params = model.init(jax.random.PRNGKey(0), np.asarray(first.x)[0],
                      np.asarray(first.edge_index)[0],
                      np.asarray(first.edge_mask)[0])
  return train_lib.TrainState(params, tx.init(params), jnp.int32(0))


def make_model_tx():
  import optax
  return (glt.models.GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2),
          optax.adam(1e-2))


def run_hbm_reference(model, tx, chunk, epochs=1, shuffle=False):
  ref = glt.loader.DistScanTrainer(make_loader(False, shuffle=shuffle),
                                   model, tx, 3, chunk_size=chunk)
  state = init_state(model, make_loader(False), tx)
  out = []
  for _ in range(epochs):
    state, losses, _ = ref.run_epoch(state)
    out.append(np.asarray(losses))
  return state, out


def test_tiered_dist_scan_bit_identical_ragged_tail_and_epoch2():
  """The acceptance bar: losses + params bit-identical to the all-HBM
  DistScanTrainer — with a ragged tail batch (38 seeds / global batch
  4 -> 9 full + 1 masked tail = 10 steps) and a tail chunk (K=4 ->
  chunks of 4, 4, 2) — at 5x per-shard oversubscription, within the
  ceil(steps/K)+2 budget, for TWO epochs (stream continuation)."""
  import jax
  model, tx = make_model_tx()
  state_ref, (l1_ref, l2_ref) = run_hbm_reference(model, tx, chunk=4,
                                                  epochs=2)

  gc.collect()
  c0 = glt_metrics.default_registry().counters()
  tmp = tempfile.mkdtemp(prefix='glt_dist_oversub_')
  loader = make_loader(True, spill_dir=tmp)
  trainer = TieredDistScanTrainer(loader, model, tx, 3, chunk_size=4)
  state = init_state(model, make_loader(False), tx)
  with glt.utils.count_dispatches() as dc:
    state, l1, _ = trainer.run_epoch(state)
  # budget: 1 plan prologue + ceil(10/4) chunks + 1 concat
  assert dc.total <= -(-10 // 4) + 2, dc
  assert dc.counts['dist_epoch_seeds'] == 1
  assert dc.counts['dist_scan_chunk'] == 3
  np.testing.assert_array_equal(np.asarray(l1), l1_ref)

  # the plan is real: rows staged beyond the hot prefix, and the
  # per-shard oversubscription factor clears the >= 4x gate
  plan = trainer.last_plan
  assert plan is not None and plan.stats()['planned_rows'] > 0
  assert plan.hot_prefix_rows == HOT_PREFIX
  n_part = trainer._store.n_max
  assert n_part / HOT_PREFIX >= 4, (n_part, HOT_PREFIX)
  c1 = glt_metrics.default_registry().counters()
  assert c1.get('storage.dist_staged_rows', 0) > c0.get(
      'storage.dist_staged_rows', 0)

  # epoch 2: the fold_in stream and permutation counters advanced
  # identically, so the continuation still matches bit for bit
  state, l2, _ = trainer.run_epoch(state)
  np.testing.assert_array_equal(np.asarray(l2), l2_ref)
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  trainer.close()


@pytest.mark.slow  # tier-1 budget (PR 16): chaos degrade variant of the
# ragged-tail bit-identity test above — same trainer, same equivalence
def test_tiered_dist_scan_chaos_degrades_to_sync_bit_identical():
  """Armed ``storage.dist_stage`` fault: every staged slab fails on the
  worker, take() degrades to a synchronous gather of the SAME planned
  positions — the epoch completes bit-identically to the all-HBM
  reference, the fault counter fired, and the degraded reads are
  counted in storage.prefetch_miss."""
  model, tx = make_model_tx()
  _, (l_ref,) = run_hbm_reference(model, tx, chunk=4)

  gc.collect()
  c0 = glt_metrics.default_registry().counters()
  tmp = tempfile.mkdtemp(prefix='glt_dist_chaos_')
  trainer = TieredDistScanTrainer(make_loader(True, spill_dir=tmp),
                                  model, tx, 3, chunk_size=4,
                                  stage_timeout_s=5.0)
  state = init_state(model, make_loader(False), tx)
  with faults.injected('storage.dist_stage', 'raise'):
    state, losses, _ = trainer.run_epoch(state)
    _, fired = faults.stats('storage.dist_stage')
  assert fired > 0
  assert trainer._stager.degraded
  c1 = glt_metrics.default_registry().counters()
  assert c1.get('storage.prefetch_miss', 0) > c0.get(
      'storage.prefetch_miss', 0)
  np.testing.assert_array_equal(np.asarray(losses), l_ref)
  trainer.close()


def test_tiered_dist_scan_validation_errors():
  """Clear construction errors: an all-HBM DistFeature store and a
  tiered store without a hot prefix are rejected with a typed
  CapacityPlanError naming the missing per-ntype slab capacities and
  the doc anchor (docs/capacity_plans.md) — the satellite contract for
  the old bare homo-only ValueError."""
  from graphlearn_tpu.sampler import CapacityPlanError
  model, tx = make_model_tx()
  with pytest.raises(CapacityPlanError, match='TieredDistFeature') as ei:
    TieredDistScanTrainer(make_loader(False), model, tx, 3)
  assert 'docs/capacity_plans.md' in str(ei.value)
  tmp = tempfile.mkdtemp(prefix='glt_dist_val_')
  with pytest.raises(CapacityPlanError, match='hot_prefix_rows'):
    TieredDistScanTrainer(
        make_loader(True, spill_dir=tmp, hot_prefix=0), model, tx, 3)
  # dist_scan_tables itself refuses a prefixless store too
  parts, feats, node_pb, _ = ring_fixture()
  df = TieredDistFeature(NUM_PARTS, feats, node_pb, mesh=make_mesh(),
                         spill_dir=tempfile.mkdtemp())
  with pytest.raises(ValueError, match='hot_prefix_rows'):
    df.dist_scan_tables()

  # hetero stores that are NOT tiered name the typed path too — hetero
  # meshes with {ntype: TieredDistFeature} stores are fully supported
  class FakeHetero:
    class sampler:
      is_hetero = True
      dist_feature = {'u': object()}
  with pytest.raises(CapacityPlanError, match='TieredDistFeature'):
    TieredDistScanTrainer(FakeHetero(), model, tx, 3)


def test_oversubscribed_device_arrays_raises_loudly():
  """ROADMAP 2b made explicit (round 15): device_arrays() on an
  OVERSUBSCRIBED TieredDistFeature — the per-step dist loader's upload
  path — must raise naming TieredDistScanTrainer instead of silently
  uploading the full partition table (defeating the declared
  oversubscription, or OOMing at real scale). A prefixless store keeps
  the full-upload path; cpu_get is unaffected either way."""
  parts, feats, node_pb, _ = ring_fixture()
  mesh = make_mesh()
  over = TieredDistFeature(NUM_PARTS, feats, node_pb, mesh=mesh,
                           spill_dir=tempfile.mkdtemp(),
                           hot_prefix_rows=2)
  with pytest.raises(RuntimeError) as ei:
    over.device_arrays()
  msg = str(ei.value)
  assert 'TieredDistScanTrainer' in msg
  assert 'hot_prefix_rows=2' in msg
  # the host-side serving path is NOT the footgun — stays available
  ids = np.asarray([0, 3, 5], np.int64)
  expect = ids[:, None].astype(np.float32) * np.ones((1, 4), np.float32)
  np.testing.assert_array_equal(over.cpu_get(ids), expect)
  # a prefixless (non-oversubscribed) store keeps the full upload
  full = TieredDistFeature(NUM_PARTS, feats, node_pb, mesh=mesh,
                           spill_dir=tempfile.mkdtemp())
  dev = full.device_arrays()
  assert dev['feats'].shape[0] == NUM_PARTS


def test_per_step_demand_paged_get_bit_identical():
  """ISSUE 16 tentpole (c): per-step ``get()`` on an OVERSUBSCRIBED
  TieredDistFeature demand-pages automatically — hot-prefix hits
  resolve in HBM, misses stage through a per-step slab planned by the
  same ``planner.plan_exchange`` routing the scanned path uses — and
  every step's rows are BIT-IDENTICAL to a prefixless (all-HBM) store,
  FILL pads included. The new counters fire (one demand_pages tick per
  step; every staged row also lands in storage.prefetch_miss), the
  slab-program cache stays closed over pow2 caps, and device_arrays()
  keeps its loud refusal for direct full-table consumers."""
  parts, feats, node_pb, _ = ring_fixture()
  mesh = make_mesh()
  over = TieredDistFeature(NUM_PARTS, feats, node_pb, mesh=mesh,
                           spill_dir=tempfile.mkdtemp(),
                           hot_prefix_rows=HOT_PREFIX)
  full = TieredDistFeature(NUM_PARTS, feats, node_pb, mesh=mesh,
                           spill_dir=tempfile.mkdtemp())

  rng = np.random.default_rng(5)
  b, steps = 6, 4
  c0 = glt_metrics.default_registry().counters()
  for step in range(steps):
    ids = rng.integers(0, N, (NUM_PARTS, b)).astype(np.int64)
    ids[0, -1] = -1                      # a FILL pad every step
    if step == steps - 1:
      # all-hot step: every id sits inside its owner's hot prefix
      # (ids 0..2*HOT_PREFIX-1 are positions 0..HOT_PREFIX-1 on the
      # round-robin partitions), so the demand slab stages ZERO rows
      ids = np.tile(np.arange(b) % (2 * HOT_PREFIX),
                    (NUM_PARTS, 1)).astype(np.int64)
    got = np.asarray(over.get(ids))
    ref = np.asarray(full.get(ids))
    np.testing.assert_array_equal(got, ref)
    valid = ids >= 0
    np.testing.assert_array_equal(
        got[valid],
        ids[valid, None].astype(np.float32) * np.ones((1, 4),
                                                      np.float32))

  c1 = glt_metrics.default_registry().counters()
  pages = c1.get('storage.demand_pages', 0) - c0.get(
      'storage.demand_pages', 0)
  paged = c1.get('storage.demand_paged_rows', 0) - c0.get(
      'storage.demand_paged_rows', 0)
  missed = c1.get('storage.prefetch_miss', 0) - c0.get(
      'storage.prefetch_miss', 0)
  assert pages == steps
  assert paged > 0 and missed == paged
  # one batch width -> one program-cache entry; its slab caps are the
  # closed pow2 set the per-step path pages through
  assert set(over._slab_fns) == {b}
  caps = set(over._slab_fns[b])
  assert caps and all(c & (c - 1) == 0 for c in caps)
  # the demand-paged path does NOT reopen the full-upload footgun
  with pytest.raises(RuntimeError, match='TieredDistScanTrainer'):
    over.device_arrays()


def test_tiered_dist_scan_shuffle_bit_identical():
  """shuffle=True: the plan program's in-shard_map permutation draw is
  bit-identical to the base seed program's plain-jit draw, so the
  device-shuffled epoch still matches the all-HBM trainer exactly."""
  model, tx = make_model_tx()
  _, (l_ref,) = run_hbm_reference(model, tx, chunk=4, shuffle=True)
  tmp = tempfile.mkdtemp(prefix='glt_dist_shuf_')
  trainer = TieredDistScanTrainer(
      make_loader(True, spill_dir=tmp, shuffle=True), model, tx, 3,
      chunk_size=4)
  state = init_state(model, make_loader(False), tx)
  state, losses, _ = trainer.run_epoch(state)
  np.testing.assert_array_equal(np.asarray(losses), l_ref)
  trainer.close()
