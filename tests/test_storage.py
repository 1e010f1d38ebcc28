"""Out-of-core tiered feature storage (graphlearn_tpu/storage/).

Pins the subsystem's four contracts (docs/storage.md):

* **Parity** — TieredFeature is bit-exact vs the all-HBM Feature
  across tier splits (homo + hetero loader batches, local + dist
  shard), and the tiered scanned epoch's losses/params are
  bit-identical to ScanTrainer over the same draws.
* **Plan exactness** — the fused prologue plan equals an independent
  host replay of the permutation + sampler streams, shuffle on or off.
* **Overlap** — under a deterministic slow-device stub, chunk c+1's
  slab finishes staging before chunk c is acked (the double buffer
  actually overlaps).
* **Degradation** — a failed staging worker (armed storage.stage
  fault) degrades to synchronous reads, bit-identically, with the
  prefetch_miss counter and fault counter visible.

Runs under GLT_STRICT (conftest): the tiered epoch region executes
with jax.transfer_guard('disallow') — every slab upload and the one
plan fetch are explicit by construction.
"""
import time

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu import metrics
from graphlearn_tpu.models import GraphSAGE, train as train_lib
from graphlearn_tpu.storage import (ChunkStager, DiskTier, TieredDistFeature,
                                    TieredFeature, TieredScanTrainer,
                                    planner, pow2_slab_cap)
from graphlearn_tpu.utils import faults


# ---------------------------------------------------------------- fixtures

N, F, CLASSES = 96, 6, 3


def make_dataset(store_fn=None, n=N, f=F, seed=0):
  rng = np.random.default_rng(seed)
  rows = np.repeat(np.arange(n), 4)
  cols = (rows + rng.integers(1, n, rows.shape[0])) % n
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=n)
  feat = rng.standard_normal((n, f)).astype(np.float32)
  if store_fn is None:
    ds.init_node_features(feat)
  else:
    ds.node_features = store_fn(feat)
  ds.init_node_labels(rng.integers(0, CLASSES, n))
  return ds, feat


def make_loader(ds, num_seeds=44, **kw):
  kw.setdefault('batch_size', 8)
  kw.setdefault('shuffle', False)
  kw.setdefault('seed', 0)
  pool = (np.random.default_rng(9).permutation(N)[:num_seeds]
          .astype(np.int64))
  return glt.loader.NeighborLoader(ds, [3, 2], pool, **kw)


# ------------------------------------------------------------------- disk


def test_disk_tier_roundtrip(tmp_path):
  arr = np.arange(100 * 5, dtype=np.float32).reshape(100, 5)
  for fmt in ('npy', 'raw'):
    t = DiskTier.write(str(tmp_path / fmt), arr, rows_per_chunk=17,
                       fmt=fmt)
    assert t.shape == (100, 5) and t.num_chunks == 6
    ids = np.array([0, 99, 17, 16, 5, 5, 50, 84])   # chunk-boundary mix
    np.testing.assert_array_equal(t.gather(ids), arr[ids])
    # reopen from meta alone
    t2 = DiskTier(str(tmp_path / fmt))
    np.testing.assert_array_equal(t2.gather(ids), arr[ids])
  with pytest.raises(IndexError):
    t.gather(np.array([100]))
  with pytest.raises(ValueError):
    DiskTier.create_empty(str(tmp_path / 'bad'), 4, 4, np.float32,
                          fmt='hdf5')


def test_disk_tier_streaming_write(tmp_path):
  """create_empty + write_rows spanning chunk boundaries — the
  materializer's spill path."""
  arr = np.random.default_rng(1).standard_normal((50, 4)).astype(
      np.float32)
  t = DiskTier.create_empty(str(tmp_path / 'w'), 50, 4, np.float32,
                            rows_per_chunk=16, fmt='raw')
  t.write_rows(10, arr[10:45])     # crosses three chunk files
  np.testing.assert_array_equal(t.gather(np.arange(10, 45)),
                                arr[10:45])
  np.testing.assert_array_equal(t.gather(np.array([0, 49])),
                                np.zeros((2, 4), np.float32))


# ---------------------------------------------------------------- tiered


@pytest.mark.parametrize('hot,warm', [(0, 40), (16, 30), (0, 0),
                                      (N, 0)])
def test_tiered_feature_parity(tmp_path, hot, warm):
  """Bit-exact vs data.Feature across tier splits, including pad (-1)
  slots and the all-hot (device_table) split."""
  feat = (np.random.default_rng(0).standard_normal((N, F))
          .astype(np.float32))
  base = glt.data.Feature(feat, split_ratio=0.2)
  tf = TieredFeature(feat, hot_rows=hot, warm_rows=warm,
                     spill_dir=str(tmp_path / f'sp{hot}_{warm}'))
  assert tf.shape == (N, F) and len(tf) == N
  occ = tf.tier_occupancy()
  assert occ['hot'] + occ['warm'] + occ['disk'] == N
  ids = np.array([0, 15, 16, 45, 46, 95, 50, 5, -1, -1], np.int32)
  np.testing.assert_array_equal(np.asarray(tf[ids]),
                                np.asarray(base[ids]))
  np.testing.assert_array_equal(tf.cpu_get(np.abs(ids)),
                                feat[np.abs(ids)])
  assert (tf.device_table() is not None) == (hot == N)


def test_tiered_feature_id2index_and_ipc(tmp_path):
  """The hotness reorder rides the tiers exactly as in Feature, and
  the IPC handle reopens the disk tier by path."""
  row = np.array([0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 0, 1, 2, 4, 5])
  col = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 0, 7, 7, 7, 3, 3])
  topo = glt.data.Topology(np.stack([row, col]), layout='CSR',
                           num_nodes=10)
  feat = np.arange(10 * 4, dtype=np.float32).reshape(10, 4)
  reordered, id2index = glt.data.sort_by_in_degree(feat, 0.3, topo)
  tf = TieredFeature(reordered, hot_rows=2, warm_rows=3,
                     id2index=id2index, spill_dir=str(tmp_path / 'sp'))
  ids = np.array([7, 3, 5, 0, 9], np.int32)
  np.testing.assert_array_equal(np.asarray(tf[ids]), feat[ids])
  np.testing.assert_array_equal(tf.cpu_get(ids), feat[ids])
  clone = TieredFeature.from_ipc_handle(tf.share_ipc())
  np.testing.assert_array_equal(np.asarray(clone[ids]), feat[ids])
  with pytest.raises(AttributeError):
    _ = tf.feature_array     # no resident full table, by design


def test_tiered_feature_hetero_loader_parity(tmp_path):
  """Per-type TieredFeature stores through the hetero loader's mixed
  collate path: batches bit-match the all-RAM Feature loader."""
  ei = {('user', 'buys', 'item'): np.array([[0, 1, 2, 3], [0, 0, 1, 1]]),
        ('item', 'rev_buys', 'user'): np.array([[0, 0, 1, 1],
                                                [0, 1, 2, 3]])}
  rng = np.random.default_rng(3)
  nfeat = {'user': rng.standard_normal((4, 5)).astype(np.float32),
           'item': rng.standard_normal((2, 5)).astype(np.float32)}

  def build(tiered):
    ds = glt.data.Dataset(edge_dir='out')
    ds.init_graph(ei, graph_mode='CPU',
                  num_nodes={('user', 'buys', 'item'): 4,
                             ('item', 'rev_buys', 'user'): 2})
    if tiered:
      ds.node_features = {
          t: TieredFeature(v, hot_rows=1, warm_rows=1,
                           spill_dir=str(tmp_path / f'sp_{t}'))
          for t, v in nfeat.items()}
    else:
      ds.init_node_features({t: v.copy() for t, v in nfeat.items()})
    fan = {('user', 'buys', 'item'): [2], ('item', 'rev_buys', 'user'): [2]}
    return glt.loader.NeighborLoader(ds, fan, ('user', np.arange(4)),
                                    batch_size=2, seed=0)

  for a, b in zip(build(False), build(True)):
    for t in a.x:
      np.testing.assert_array_equal(np.asarray(a.x[t]),
                                    np.asarray(b.x[t]))


def test_tiered_dist_feature_parity(tmp_path):
  """dist shard: TieredDistFeature (rows on disk) vs DistFeature (rows
  in RAM) — bit-exact get()/cpu_get(), identical on-device stats,
  upload assembled straight from the mmaps."""
  import jax
  from jax.sharding import Mesh

  from graphlearn_tpu.distributed.dist_feature import DistFeature
  P = 4
  rng = np.random.default_rng(0)
  n = 128
  feat = rng.standard_normal((n, F)).astype(np.float32)
  pb = rng.integers(0, P, n).astype(np.int32)
  parts = [(np.where(pb == p)[0].astype(np.int64), feat[pb == p])
           for p in range(P)]
  mesh = Mesh(np.array(jax.devices()[:P]), ('g',))
  a = DistFeature(P, parts, pb, mesh=mesh, split_ratio=0.25)
  b = TieredDistFeature(P, parts, pb, mesh=mesh, split_ratio=0.25,
                        spill_dir=str(tmp_path), rows_per_chunk=19)
  ids = rng.integers(0, n, (P, 16)).astype(np.int32)
  np.testing.assert_array_equal(np.asarray(a.get(ids)),
                                np.asarray(b.get(ids)))
  assert a.stats() == b.stats()
  flat = ids.reshape(-1)
  np.testing.assert_array_equal(a.cpu_get(flat), b.cpu_get(flat))
  tb = b.tier_bytes()
  assert tb['disk_bytes'] == n * F * 4
  assert tb['resident_bytes'] < tb['disk_bytes']
  with pytest.raises(ValueError):
    TieredDistFeature(P, parts, pb, mesh=mesh)   # no spill_dir


# ------------------------------------------------------- scanned trainer


def _tiered_run(tmp, shuffle, template, tx, model, hot=16, warm=30,
                num_seeds=44, chunk=4, **trainer_kw):
  """A fresh TieredScanTrainer epoch over its own spilled store."""
  import jax
  ds, _ = make_dataset(lambda f: TieredFeature(
      f, hot_rows=hot, warm_rows=warm, spill_dir=str(tmp / 'sp')))
  state, _ = train_lib.create_train_state(
      model, jax.random.PRNGKey(0), template, optimizer=tx)
  tr = TieredScanTrainer(make_loader(ds, num_seeds, shuffle=shuffle),
                         model, tx, CLASSES, chunk_size=chunk,
                         **trainer_kw)
  state, losses, _ = tr.run_epoch(state)
  return state, losses, tr


@pytest.fixture(scope='module')
def hbm_run():
  """One all-HBM ScanTrainer reference (shuffle=False, 44 seeds /
  batch 8 -> 5 full + tail, K=4 -> tail chunk), shared across the
  parity/chaos tests so the reference epoch compiles once."""
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  ds, _ = make_dataset()
  template = train_lib.batch_to_dict(next(iter(make_loader(ds))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  tr = glt.loader.ScanTrainer(make_loader(ds, 44), model, tx, CLASSES,
                              chunk_size=4)
  state, losses, _ = tr.run_epoch(state)
  return dict(model=model, template=template, tx=tx, trainer=tr,
              state=state, losses=np.asarray(losses))


def test_tiered_scan_bit_parity_and_budget(tmp_path, hbm_run):
  """The tentpole contract: a scanned epoch over a TieredFeature whose
  store is ~6x oversubscribed vs the hot tier is BIT-IDENTICAL to the
  all-HBM ScanTrainer — losses and params — at the unchanged
  ceil(steps/K)+2 dispatch budget, with a ragged tail batch and a tail
  chunk. Epoch 2 continues both streams identically."""
  import jax
  state_b, losses_b, tr_b = _tiered_run(tmp_path, False,
                                        hbm_run['template'],
                                        hbm_run['tx'], hbm_run['model'])
  np.testing.assert_array_equal(hbm_run['losses'], np.asarray(losses_b))
  for x, y in zip(jax.tree_util.tree_leaves(hbm_run['state'].params),
                  jax.tree_util.tree_leaves(state_b.params)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
  # dispatch budget: ceil(6/4) + 2 == 4, measured
  from graphlearn_tpu.utils.trace import count_dispatches
  with count_dispatches() as counter:
    state_b, losses_b2, _ = tr_b.run_epoch(state_b)
  assert counter.total == -(-6 // 4) + 2
  state_a, losses_a2, _ = hbm_run['trainer'].run_epoch(hbm_run['state'])
  np.testing.assert_array_equal(np.asarray(losses_a2),
                                np.asarray(losses_b2))
  # staging accounting: every planned row was staged by the worker
  assert tr_b.last_plan.stats()['planned_rows'] > 0
  tr_b.close()


def test_tiered_scan_shuffle_parity(tmp_path):
  """shuffle=True: both trainers draw the SAME on-device permutation
  (same perm seed), so the tiered epoch stays bit-identical. (The
  shuffle=True PLAN path stays tier-1 via
  test_plan_matches_host_replay[True].)"""
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  ds, _ = make_dataset()
  template = train_lib.batch_to_dict(next(iter(make_loader(ds))))
  state_a, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                             template)
  tr_a = glt.loader.ScanTrainer(make_loader(ds, 44, shuffle=True),
                                model, tx, CLASSES, chunk_size=4)
  state_a, losses_a, _ = tr_a.run_epoch(state_a)
  _, losses_b, tr_b = _tiered_run(tmp_path, True, template, tx, model)
  np.testing.assert_array_equal(np.asarray(losses_a),
                                np.asarray(losses_b))
  tr_b.close()


@pytest.mark.parametrize('shuffle', [False, True])
def test_plan_matches_host_replay(tmp_path, shuffle):
  """Prologue plan correctness: the fused device plan (sampler replay
  inside the epoch_seeds program) == an independent eager host replay
  of the permutation + fold_in streams — per chunk, exactly."""
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  ds, _ = make_dataset(lambda f: TieredFeature(
      f, hot_rows=16, warm_rows=30, spill_dir=str(tmp_path / 'sp')))
  loader = make_loader(ds, 44, shuffle=shuffle)
  template = train_lib.batch_to_dict(
      next(iter(make_loader(make_dataset()[0]))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  tr = TieredScanTrainer(loader, model, tx, CLASSES, chunk_size=4)
  store = ds.node_features
  expected = planner.plan_epoch_host(
      loader.sampler, loader.input_seeds,
      jax.random.fold_in(tr._perm_key, 0), steps=6, batch=8,
      shuffle=shuffle, chunk_size=4, hot_rows=store.hot_rows,
      warm_rows=store.warm_rows)
  state, _, _ = tr.run_epoch(state)
  got = tr.last_plan
  assert got.num_chunks == expected.num_chunks == 2
  for a, b in zip(expected.chunk_rows, got.chunk_rows):
    np.testing.assert_array_equal(a, b)
  assert all(c == pow2_slab_cap(c) for c in got.slab_caps())
  tr.close()


def test_chunk_boundary_overlap(tmp_path):
  """Stage of chunk c+1 completes BEFORE chunk c's ack when the device
  is slow: wrap the chunk dispatch in a deterministic blocking stub
  (block_until_ready + sleep >> disk gather time) and compare the
  stager's timestamps."""
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  ds, _ = make_dataset(lambda f: TieredFeature(
      f, hot_rows=8, warm_rows=8, spill_dir=str(tmp_path / 'sp')))
  loader = make_loader(ds, 40, shuffle=False)   # 5 chunks of 1
  template = train_lib.batch_to_dict(
      next(iter(make_loader(make_dataset()[0]))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  tr = TieredScanTrainer(loader, model, tx, CLASSES, chunk_size=1)
  real = tr._chunk_fn

  def slow_chunk(*args, **kw):
    out = real(*args, **kw)
    jax.block_until_ready(out[0])
    time.sleep(0.25)
    return out

  tr._chunk_fn = slow_chunk
  state, _, _ = tr.run_epoch(state)
  st, ack = tr._stager.stage_done_t, tr._stager.ack_t
  assert not tr._stager.degraded
  # with max_ahead=2, chunk c+1 was staged while chunk c (or earlier)
  # trained: its staging must beat chunk c's ack
  for c in range(0, 3):
    assert st[c + 1] < ack[c], (c, st, ack)
  tr.close()


def test_pow2_staging_shape_closure(tmp_path):
  """One executable per (chunk length, slab cap) shape: epoch 2 of a
  shuffle=False run presents the identical pow2 shape set, so the
  scan_chunk site compiles ZERO new programs (asserted through the
  program observatory, under GLT_STRICT)."""
  import jax
  from graphlearn_tpu.metrics import programs
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  ds, _ = make_dataset(lambda f: TieredFeature(
      f, hot_rows=16, warm_rows=30, spill_dir=str(tmp_path / 'sp')))
  loader = make_loader(ds, 44, shuffle=False)
  template = train_lib.batch_to_dict(
      next(iter(make_loader(make_dataset()[0]))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  tr = TieredScanTrainer(loader, model, tx, CLASSES, chunk_size=4)
  state, _, _ = tr.run_epoch(state)
  before = programs.stats().get('scan_chunk', {}).get('compiles', 0)
  state, _, _ = tr.run_epoch(state)
  after = programs.stats().get('scan_chunk', {}).get('compiles', 0)
  assert after == before, 'steady-state tiered epoch retraced'
  assert all(c == pow2_slab_cap(c) for c in tr.last_plan.slab_caps())
  tr.close()


def test_degraded_sync_fallback_chaos(tmp_path, hbm_run):
  """Armed storage.stage fault: the staging worker fails, the epoch
  degrades to synchronous on-demand reads — and completes BIT-
  IDENTICALLY to the all-HBM reference, with the fault +
  prefetch_miss counters visible. Never a wrong batch."""
  import jax
  ds, _ = make_dataset(lambda f: TieredFeature(
      f, hot_rows=16, warm_rows=30,
      spill_dir=str(tmp_path / 'faulted')))
  loader = make_loader(ds, 44, shuffle=False)
  state, _ = train_lib.create_train_state(
      hbm_run['model'], jax.random.PRNGKey(0), hbm_run['template'],
      optimizer=hbm_run['tx'])
  tr = TieredScanTrainer(loader, hbm_run['model'], hbm_run['tx'],
                         CLASSES, chunk_size=4, stage_timeout_s=5.0)
  miss0 = metrics.default_registry().counters().get(
      'storage.prefetch_miss', 0)
  with faults.injected('storage.stage', 'raise'):
    state, losses_b, _ = tr.run_epoch(state)
    _, fired = faults.stats('storage.stage')
  assert fired >= 1
  assert tr._stager.degraded
  miss1 = metrics.default_registry().counters().get(
      'storage.prefetch_miss', 0)
  assert miss1 > miss0
  # the tiered run under fault == the ALL-HBM ScanTrainer's losses
  np.testing.assert_array_equal(hbm_run['losses'],
                                np.asarray(losses_b))
  tr.close()


def test_scan_trainer_stage_ack_hooks(tmp_path):
  """The generic chunk-boundary hooks on the base ScanTrainer (the
  seam DistScanTrainer shares): stage_hook fires before each chunk
  dispatch, ack_hook after, in chunk order."""
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  ds, _ = make_dataset()
  loader = make_loader(ds, 44)
  template = train_lib.batch_to_dict(next(iter(make_loader(ds))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  tr = glt.loader.ScanTrainer(loader, model, tx, CLASSES, chunk_size=4)
  events = []
  tr.stage_hook = lambda c, start, k: events.append(('stage', c, k))
  tr.ack_hook = lambda c, start, k: events.append(('ack', c, k))
  state, _, _ = tr.run_epoch(state)
  assert events == [('stage', 0, 4), ('ack', 0, 4),
                    ('stage', 1, 2), ('ack', 1, 2)]


# -------------------------------------------------- observability + flight


def test_storage_flight_and_metrics(tmp_path, monkeypatch):
  """The tiered epoch's flight record carries the per-epoch staging
  deltas in its 'storage' field, and the staging metrics land in the
  typed registry under their registered names."""
  import jax
  from graphlearn_tpu.metrics import flight
  log = tmp_path / 'run.jsonl'
  monkeypatch.setenv('GLT_RUN_LOG', str(log))
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  ds, _ = make_dataset(lambda f: TieredFeature(
      f, hot_rows=16, warm_rows=30, spill_dir=str(tmp_path / 'sp')))
  loader = make_loader(ds, 44)
  template = train_lib.batch_to_dict(
      next(iter(make_loader(make_dataset()[0]))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  tr = TieredScanTrainer(loader, model, tx, CLASSES, chunk_size=4)
  state, _, _ = tr.run_epoch(state)
  recs = flight.read_records(str(log))
  rec = [r for r in recs if r['emitter'] == 'TieredScanTrainer'][-1]
  assert rec['storage'].get('storage.staged_rows', 0) > 0
  assert rec['storage'].get('storage.staged_bytes', 0) > 0
  assert rec['config']['hot_rows'] == 16
  snap = metrics.snapshot()
  assert 'storage.stage_ms' in snap['histograms']
  from graphlearn_tpu.metrics.logcheck import validate_flight_record
  assert validate_flight_record(rec) == []
  tr.close()


def test_stager_standalone_degrades_on_timeout(tmp_path):
  """A stalled worker (delay fault) trips the take() timeout and the
  consumer gathers synchronously — same bytes."""
  feat = (np.random.default_rng(0).standard_normal((64, 4))
          .astype(np.float32))
  tf = TieredFeature(feat, hot_rows=8, warm_rows=8,
                     spill_dir=str(tmp_path / 'sp'))
  stager = ChunkStager(tf, max_ahead=1, timeout_s=0.2)
  rows = np.arange(20, 40, dtype=np.int64)
  with faults.injected('storage.stage', 'delay', delay=1.0):
    stager.begin_epoch([rows])
    ids, slab = stager.take(0)
  assert stager.degraded
  valid = ids != np.iinfo(np.int32).max
  np.testing.assert_array_equal(slab[valid.nonzero()[0]], feat[rows])
  stager.close()
  # the promote site (slab -> ring hand-off) degrades the same way; a
  # fresh stager with a patient timeout so the worker (not the clock)
  # trips the fault
  stager2 = ChunkStager(tf, max_ahead=1, timeout_s=10.0)
  with faults.injected('storage.promote', 'raise'):
    stager2.begin_epoch([rows])
    ids2, slab2 = stager2.take(0)
    _, fired = faults.stats('storage.promote')
  assert fired >= 1 and stager2.degraded
  np.testing.assert_array_equal(slab2, slab)
  stager2.close()
  # close() mid-epoch drains the queue (stale chunk ids AND the None
  # sentinel): the next epoch's fresh worker must stage ASYNC again,
  # not die on a leftover sentinel and silently degrade every take()
  stager3 = ChunkStager(tf, max_ahead=1, timeout_s=10.0)
  stager3.begin_epoch([rows, rows + 1])
  stager3.take(0)           # queues chunk 1
  stager3.close()           # chunk 1 (or the sentinel) still queued
  stager3.begin_epoch([rows])
  ids3, _ = stager3.take(0)
  assert not stager3.degraded
  np.testing.assert_array_equal(ids3, ids2)
  stager3.close()


# ----------------------------------------------------------- serving spill


def test_materializer_spill_and_tiered_store(tmp_path):
  """serving: the donated layer stores spill to disk tiers, and the
  final table serves through a TieredEmbeddingStore bit-identically to
  the all-HBM EmbeddingStore."""
  import jax
  ds, _ = make_dataset(n=64)
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  batch = dict(x=np.zeros((4, F), np.float32),
               edge_index=np.zeros((2, 4), np.int32),
               edge_mask=np.ones(4, bool))
  params = model.init(jax.random.PRNGKey(0), batch['x'],
                      batch['edge_index'], batch['edge_mask'])
  from graphlearn_tpu.serving.materialize import EmbeddingMaterializer
  mat = EmbeddingMaterializer(ds, model, params, block_size=16,
                              chunk_size=2, spill_dir=str(tmp_path))
  mat.materialize()
  assert sorted(mat.spilled) == ['0', '1']    # one tier per layer pass
  tiered = mat.tiered_embedding_store(hot_rows=8, warm_rows=16)
  base = mat.embedding_store()
  ids = np.array([0, 5, 63, 33, -1, -1, 12, 40])
  mask = ids >= 0
  np.testing.assert_array_equal(
      base.fetch(base.lookup(np.maximum(ids, 0), mask)),
      tiered.fetch(tiered.lookup(ids, mask)))
  with pytest.raises(NotImplementedError):
    tiered.update_rows(np.array([1]), np.zeros((1, CLASSES), np.float32))
