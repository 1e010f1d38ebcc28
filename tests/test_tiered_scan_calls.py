"""What PR 41 asks of the tiered scanned epoch at the size of a real table
(storage/scan.py, storage/tiered.py, data/graph.py), held at a toy size:

* a call plans the steps it runs: the plan of ``run_epoch(max_steps=m)``
  holds that call's chunks only, two calls of 32 steps (the second resumed
  at the boundary) train bit for bit what one call of 64 trains, and a call
  of 32 trains what the all-HBM trainer's call of 32 trains;
* ``TieredFeature.from_tiers`` (hot prefix on the device, warm tier adopted)
  serves what the array constructor serves and trains the same epoch;
* the gather's scopes are in the chunk's lowered text and ``glt.plan`` is
  not; the plan program carries ``glt.plan``;
* the spans and counters of a call;
* ``Topology.from_csr`` samples what the sorting constructor samples, and
  places no edge ids.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu import metrics
from graphlearn_tpu.metrics import registry_names as names, spans
from graphlearn_tpu.models import GraphSAGE, train as train_lib
from graphlearn_tpu.storage import (TieredFeature, TieredScanTrainer,
                                    planner, pow2_slab_cap)

N, F, CLASSES, B, K = 400, 6, 3, 4, 16
HOT = 40


def _graph(seed=0):
  rng = np.random.default_rng(seed)
  rows = np.repeat(np.arange(N), 4)
  cols = (rows + rng.integers(1, N, rows.shape[0])) % N
  feat = rng.standard_normal((N, F)).astype(np.float32)
  return rows, cols, feat, rng.integers(0, CLASSES, N)


def _dataset(store_fn=None):
  rows, cols, feat, labels = _graph()
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=N)
  if store_fn is None:
    ds.init_node_features(feat)
  else:
    ds.node_features = store_fn(feat)
  ds.init_node_labels(labels)
  return ds, feat


def _loader(ds, shuffle=True):
  pool = np.random.default_rng(9).permutation(N)[:300].astype(np.int64)
  return glt.loader.NeighborLoader(ds, [3, 2], pool, batch_size=B,
                                   shuffle=shuffle, drop_last=True, seed=5)


def _from_tiers(feat):
  import jax
  return TieredFeature.from_tiers(jax.device_put(feat[:HOT]), feat[HOT:])


@pytest.fixture(scope='module')
def parts():
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  ds, _ = _dataset()
  template = train_lib.batch_to_dict(next(iter(_loader(ds))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  return dict(model=model, tx=tx, state=state)


def _fresh(parts):
  import jax
  return jax.tree.map(lambda a: a.copy(), parts['state'])


def _trainer(parts, store_fn=_from_tiers, **kw):
  ds, _ = _dataset(store_fn)
  cls = glt.loader.ScanTrainer if store_fn is None else TieredScanTrainer
  return cls(_loader(ds), parts['model'], parts['tx'], CLASSES,
             chunk_size=K, **kw)


def _same_tree(a, b):
  import jax
  for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------- a call costs the steps it runs


def test_two_calls_of_32_train_what_one_call_of_64_trains(parts):
  """75 steps an epoch, K = 16. One call of 64; against it a call of 32
  and then the same epoch resumed at step 32 (epoch index and sampler
  counter put back, as ``recovery.resume_epoch`` puts them): losses and
  final state bit for bit, and each call's plan holds its own chunks."""
  one = _trainer(parts)
  assert one._epoch_steps() == 75
  state_a, losses_a, _ = one.run_epoch(_fresh(parts), max_steps=64)
  assert one.last_plan.num_chunks == 4          # not ceil(75 / 16) = 5
  assert all(r.size for r in one.last_plan.chunk_rows)
  one.close()

  two = _trainer(parts)
  state_b, first, _ = two.run_epoch(_fresh(parts), max_steps=32)
  assert two.last_plan.num_chunks == 2
  two._epochs -= 1
  two._sampler._call_count -= 32
  state_b, second, _ = two.run_epoch(state_b, max_steps=64, start_step=32)
  plan = two.last_plan
  assert plan.num_chunks == 4
  assert [r.size > 0 for r in plan.chunk_rows] == [False, False, True, True]
  assert two._sampler._call_count == one._sampler._call_count
  two.close()
  np.testing.assert_array_equal(
      np.asarray(losses_a),
      np.concatenate([np.asarray(first), np.asarray(second)]))
  _same_tree(state_a.params, state_b.params)
  _same_tree(state_a.opt_state, state_b.opt_state)


def test_the_plan_program_replays_the_calls_steps_only(parts):
  """The plan program's row blocks cover ``[start, steps)``: 32 steps of
  a 75-step epoch are two [16, cap] blocks, and the seed matrix is still
  the epoch's (what the chunks slice is what the all-HBM chunks slice)."""
  import jax
  tr = _trainer(parts)
  fargs = tr._sampler._fused_args()
  tr._seeds_dev = jax.device_put(
      np.asarray(tr.loader.input_seeds, dtype=np.int32))
  args = (fargs, tr._id2i, tr._seeds_dev, tr._perm_key, tr._sampler._key,
          jax.device_put(np.int32(1)))
  seed_mat, _, blocks, seen = tr._seed_fn(*args, 75, 0, 32)
  assert seed_mat.shape == (75, B)
  assert [b.shape[0] for b in blocks] == [16, 16]
  _, _, tail, _ = tr._seed_fn(*args, 75, 32, 40)
  assert [b.shape[0] for b in tail] == [8]
  lookups, hits = (int(v) for v in seen)
  assert 0 < hits < lookups <= 32 * blocks[0].shape[1]
  # the same steps' rows whichever call plans them
  _, _, whole, _ = tr._seed_fn(*args, 75, 0, 40)
  np.testing.assert_array_equal(np.asarray(whole[2]), np.asarray(tail[0]))
  tr.close()


def test_a_call_of_32_trains_what_the_all_hbm_call_trains(parts):
  hbm = _trainer(parts, store_fn=None)
  tiered = _trainer(parts)
  sa, sb = _fresh(parts), _fresh(parts)
  for _ in range(2):        # the second call: a fresh permutation, both
    sa, la, _ = hbm.run_epoch(sa, max_steps=32)
    sb, lb, _ = tiered.run_epoch(sb, max_steps=32)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
  _same_tree(sa.params, sb.params)
  tiered.close()


def test_chunk_misses_is_the_sorted_unique_of_the_non_hot_rows():
  rng = np.random.default_rng(3)
  block = rng.integers(0, 1000, (16, 333)).astype(np.int32)
  want = np.unique(block)
  want = want[want >= 100]
  got = planner.chunk_misses(block, 100)
  np.testing.assert_array_equal(got, want)
  assert got.dtype == np.int64
  assert planner.chunk_misses(block, 1000).size == 0
  plan = planner.plan_from_rows(block, 4, 100)
  np.testing.assert_array_equal(plan.rows(0),
                                planner.chunk_misses(block[:4], 100))


# ----------------------------------------------------- the no-copy door


def test_from_tiers_serves_and_trains_what_the_array_store_does(parts):
  _, _, feat, _ = _graph()
  i2i = np.random.default_rng(1).permutation(N).astype(np.int32)
  import jax
  arr = TieredFeature(feat, hot_rows=HOT, id2index=i2i)
  warm = feat[HOT:]
  door = TieredFeature.from_tiers(jax.device_put(feat[:HOT]), warm,
                                  id2index=i2i)
  assert door._warm_np is warm and door._hot_np is None   # adopted, no copy
  assert (door.hot_rows, door.warm_rows, door.disk_rows) == (HOT, N - HOT, 0)
  assert door.shape == arr.shape == (N, F)
  ids = np.array([0, 5, 399, 17, 17, 250, 39, 40])
  np.testing.assert_array_equal(door.cpu_get(ids), arr.cpu_get(ids))
  np.testing.assert_array_equal(door.cpu_get(ids), feat[i2i[ids]])
  np.testing.assert_array_equal(np.asarray(door[ids]), np.asarray(arr[ids]))
  # share_ipc hands the hot prefix over as a host array, fetched
  back = TieredFeature.from_ipc_handle(door.share_ipc())
  np.testing.assert_array_equal(back.cpu_get(ids), arr.cpu_get(ids))
  with pytest.raises(ValueError, match='at least one row'):
    TieredFeature.from_tiers(jax.device_put(feat[:0]), feat)
  with pytest.raises(ValueError, match='warm is'):
    TieredFeature.from_tiers(jax.device_put(feat[:HOT]),
                             feat[HOT:].astype(np.float64))
  # the same epoch, bit for bit
  a = _trainer(parts, store_fn=lambda f: TieredFeature(f, hot_rows=HOT))
  b = _trainer(parts)
  _, la, _ = a.run_epoch(_fresh(parts), max_steps=32)
  _, lb, _ = b.run_epoch(_fresh(parts), max_steps=32)
  np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
  a.close()
  b.close()


def test_stage_gather_writes_into_the_slab(parts):
  _, _, feat, _ = _graph()
  store = _from_tiers(feat)
  rows = np.array([HOT, 77, 399, 41])
  out = np.full((4, F), np.nan, np.float32)
  got = store.stage_gather(rows, out=out)
  assert got is out
  np.testing.assert_array_equal(out, feat[rows])
  np.testing.assert_array_equal(store.stage_gather(rows), feat[rows])
  with pytest.raises(IndexError):
    store.stage_gather(np.array([HOT - 1]))


# ------------------------------------------------------ scopes, spans, counts


def _lowered(parts):
  """(chunk text, plan text) with debug info, of one tiered trainer."""
  import jax
  tr = _trainer(parts)
  kept = {}

  class Stop(Exception):
    pass

  real = tr._chunk_fn

  def keep(*args):
    kept['args'] = args
    raise Stop

  tr._chunk_fn = keep
  with pytest.raises(Stop):
    tr.run_epoch(_fresh(parts), max_steps=32)
  tr._chunk_fn = real
  chunk = real.lower(*kept['args']).as_text(debug_info=True)
  fargs = tr._sampler._fused_args()
  plan = tr._seed_fn.lower(
      fargs, tr._id2i, tr._seeds_dev, tr._perm_key, tr._sampler._key,
      jax.device_put(np.int32(1)), 75, 0, 32).as_text(debug_info=True)
  tr.close()
  return chunk, plan


def test_the_gathers_scopes_are_in_the_chunk_and_the_plan_is_not(parts):
  chunk, plan = _lowered(parts)
  for part in ('hot', 'lookup', 'rows'):
    scope = f'glt.collate/tier/{part}'
    assert scope in names.REGISTERED_SCOPES
    assert scope in chunk, scope
  assert 'glt.plan' not in chunk
  assert 'glt.plan' in plan and 'glt.plan' in names.REGISTERED_SCOPES
  assert 'glt.collate/tier' not in plan       # the plan gathers no row
  assert 'glt.train' in chunk and 'glt.train' not in plan


def test_a_calls_spans_and_counters(parts):
  want = ('storage.lookups', 'storage.hot_hits', 'storage.planned_rows',
          'storage.slab_cap_rows')
  assert set(want) <= names.REGISTERED_METRICS
  assert {'epoch.plan', 'epoch.stage_wait', 'epoch.upload'} <= \
      names.REGISTERED_SPANS
  before = {k: glt.utils.counter_get(k) for k in want}
  spans.reset()
  tr = _trainer(parts)
  tr.run_epoch(_fresh(parts), max_steps=32)
  got = {k: glt.utils.counter_get(k) - before[k] for k in want}
  rows = [int(r.size) for r in tr.last_plan.chunk_rows]
  assert got['storage.planned_rows'] == sum(rows) > 0
  # a slab is never smaller than the largest the stager has made
  caps = np.maximum.accumulate([pow2_slab_cap(r) for r in rows])
  assert got['storage.slab_cap_rows'] == int(caps.sum())
  assert 0 < got['storage.hot_hits'] < got['storage.lookups']
  rec = [s['name'] for s in spans.export()]
  assert rec.count('epoch.plan') == 1
  assert rec.count('epoch.stage_wait') == rec.count('epoch.upload') == \
      rec.count('epoch.chunk') == 2
  assert rec.count('storage.stage') == 2
  staged = [s for s in spans.export() if s['name'] == 'storage.stage']
  assert sorted(s['attrs']['rows'] for s in staged) == sorted(rows)
  assert metrics.snapshot()['counters'].get('storage.prefetch_miss', 0) == 0 \
      or not tr._stager.degraded
  tr.close()


def test_the_tiered_trainer_still_refuses_what_it_does_not_run(parts):
  ds, _ = _dataset()
  with pytest.raises(ValueError, match='TieredFeature'):
    TieredScanTrainer(_loader(ds), parts['model'], parts['tx'], CLASSES)
  ds, _ = _dataset(_from_tiers)
  rows, cols, _, _ = _graph()
  link = glt.loader.LinkNeighborLoader(
      ds, [3, 2], edge_label_index=np.stack([rows, cols])[:, :64],
      batch_size=B)
  with pytest.raises(ValueError, match='node-seeded'):
    TieredScanTrainer(link, parts['model'], parts['tx'], CLASSES)


# ----------------------------------------------- the topology's no-sort door


def test_from_csr_samples_what_the_sorted_topology_samples():
  rows, cols, _, _ = _graph()
  full = glt.data.Topology(np.stack([rows, cols]), num_nodes=N)
  pad = np.concatenate([full.indices, np.full((37,), -1, np.int32)])
  topo = glt.data.Topology.from_csr(full.indptr, pad, num_nodes=N)
  assert topo.edge_ids is None and topo.edge_weights is None
  assert np.shares_memory(topo.indices, pad)      # adopted, not copied
  assert (topo.num_nodes, topo.num_edges) == (N, full.num_edges)
  assert topo.max_degree == full.max_degree
  np.testing.assert_array_equal(topo.degree(np.arange(N)),
                                full.degree(np.arange(N)))
  with pytest.raises(ValueError, match='CSR or CSC'):
    glt.data.Topology.from_csr(full.indptr, pad, layout='COO')
  out = []
  for t in (full, topo):
    graph = glt.data.Graph(t, 'HBM')
    ds = glt.data.Dataset(graph=graph)
    ds.init_node_labels(np.zeros(N, np.int32))
    caps = glt.sampler.estimate_frontier_caps(graph, [3, 2], B, seed=0)
    loader = glt.loader.NeighborLoader(
        ds, [3, 2], np.arange(40), batch_size=B, seed=2, dedup='map',
        frontier_caps=caps)
    b = next(iter(loader))
    out.append((caps, np.asarray(b.node), np.asarray(b.edge_index)))
  assert glt.data.Graph(topo, 'HBM').edge_ids is None    # none placed
  assert out[1][0] == out[0][0]
  np.testing.assert_array_equal(out[1][1], out[0][1])
  np.testing.assert_array_equal(out[1][2], out[0][2])


# ------------------------------------------------- the storage order's door


@pytest.mark.parametrize('n,hot', [(1000, 150), (1000, 0), (1000, 1000),
                                   (50, 7), (1, 1)])
def test_hot_first_order_is_the_full_sorts_prefix(n, hot):
  """The hot prefix is ``sort_by_in_degree``'s (hotness descending, ties by
  id), row for row; the rest keeps id order; the two maps invert each
  other."""
  score = np.random.default_rng(n + hot).integers(0, 20, n)
  order, i2i = glt.data.hot_first_order(score, hot)
  full = np.argsort(-score, kind='stable')
  np.testing.assert_array_equal(order[:hot], full[:hot])
  assert (np.diff(order[hot:]) > 0).all()
  np.testing.assert_array_equal(i2i[order], np.arange(n))
  assert order.dtype == i2i.dtype == np.int32
  with pytest.raises(ValueError, match='negative'):
    glt.data.hot_first_order(np.array([1, -1]), 1)


# ------------------------------------------ the plan's entries, two threads


def test_a_plan_entry_resolves_once_whichever_thread_asks():
  """The worker and a degraded ``take`` may both ask for a chunk's rows:
  every asker gets the same set, and the plan keeps the array, not the
  callable (more askers than cores, a short switch interval)."""
  import sys
  import threading
  from graphlearn_tpu.storage import ChunkStager
  _, _, feat, _ = _graph()
  stager = ChunkStager(_from_tiers(feat))
  rows = np.arange(HOT, HOT + 60, dtype=np.int64)
  calls = []

  def make():
    calls.append(1)
    return rows.copy()

  plan = planner.EpochPlan(chunk_size=K, hot_rows=HOT, warm_rows=N - HOT,
                           chunk_rows=[make])
  stager._plan = plan.thunks()
  got, old = [], sys.getswitchinterval()
  sys.setswitchinterval(1e-6)
  try:
    ts = [threading.Thread(target=lambda: got.append(stager._planned_rows(0)))
          for _ in range(32)]
    for t in ts:
      t.start()
    for t in ts:
      t.join(timeout=30)
    assert not any(t.is_alive() for t in ts)
  finally:
    sys.setswitchinterval(old)
  assert len(got) == 32 and calls
  for g in got:
    np.testing.assert_array_equal(g, rows)
  assert isinstance(plan.chunk_rows[0], np.ndarray)
  assert plan.stats()['planned_rows'] == 60
  ids, slab = stager._gather(rows)
  assert ids.shape == (64,) and slab.shape == (64, F)
  np.testing.assert_array_equal(slab[:60], feat[rows])
  assert (slab[60:] == 0).all() and (ids[60:] == np.iinfo(np.int32).max).all()
  # a later, smaller chunk is padded to the floor the larger one set
  assert stager._gather(rows[:5])[0].shape == (64,)
