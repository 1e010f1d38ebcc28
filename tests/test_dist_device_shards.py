"""Shards that already live on their devices (PR 35):
``DistGraph`` / ``DistFeature`` / ``DistDataset.from_device_shards``.

A partitioned graph too large to stack in host memory is handed to the
mesh engine as device arrays. What must hold: the device-built containers
are the host-built ones bit for bit (the hot cache included, whether the
hotness vector is a host or a device array); the per-step loader and the
scanned epoch over them are the per-step loader and scanned epoch over the
host-built dataset, bit for bit; caps probed through the mesh sampler hold
the epoch without overflow; the collectives have scopes of their own in
the chunk program; and the epoch publishes what its exchanges carried.
"""
import gc

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.models import train as train_lib
from graphlearn_tpu.typing import GraphPartitionData

N, P, F = 240, 4, 8


def make_mesh():
  import jax
  from jax.sharding import Mesh
  return Mesh(np.array(jax.devices()[:P]), ('g',))


@pytest.fixture(scope='module')
def world():
  """One random directed graph, partitioned by ``id % P``, host-built and
  device-built from the host build's own device arrays."""
  import jax.numpy as jnp
  rng = np.random.default_rng(0)
  rows = rng.integers(0, N, 1600)
  cols = (rng.zipf(1.6, 1600) - 1) % N          # a few hot targets
  feat = rng.normal(size=(N, F)).astype(np.float32)
  feat[3, 2] = -0.0                             # a negative zero survives
  label = rng.integers(0, 5, N).astype(np.int32)
  node_pb = (np.arange(N) % P).astype(np.int32)
  parts, fparts = [], []
  for q in range(P):
    m = node_pb[rows] == q
    parts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]),
        eids=np.nonzero(m)[0]))
    own = np.nonzero(node_pb == q)[0]
    fparts.append((own.astype(np.int64), feat[own]))
  mesh = make_mesh()
  hot = np.bincount(cols, minlength=N)
  dg = glt.distributed.DistGraph(P, 0, parts, node_pb)
  df = glt.distributed.DistFeature(P, fparts, node_pb, mesh,
                                   split_ratio=0.1, hotness=hot)
  host = glt.distributed.DistDataset(P, 0, dg, df, node_labels=label)
  ga, fa = dg.device_arrays(mesh), df.device_arrays()
  lab = np.zeros(fa['feat_ids'].shape, np.int32)
  fid = np.asarray(fa['feat_ids'])
  lab[fid < N] = label[fid[fid < N]]
  import jax
  dev = glt.distributed.DistDataset.from_device_shards(
      mesh, node_pb,
      {k: ga[k] for k in ('row_ids', 'indptr', 'indices', 'eids')},
      dict(feat_ids=fa['feat_ids'], feats=fa['feats']),
      labels=jax.device_put(lab, fa['feat_ids'].sharding),
      split_ratio=0.1, hotness=jnp.asarray(hot.astype(np.int32)))
  return dict(mesh=mesh, host=host, dev=dev, hot=hot, feat=feat,
              label=label, node_pb=node_pb, rows=rows, cols=cols)


def test_device_built_shards_equal_host_built_bit_for_bit(world):
  mesh, host, dev = world['mesh'], world['host'], world['dev']
  a, b = host.graph.device_arrays(mesh), dev.graph.device_arrays(mesh)
  assert set(a) == set(b)
  for k in a:
    assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
    assert a[k].sharding.is_equivalent_to(b[k].sharding, a[k].ndim), k
  fa = host.node_features.device_arrays()
  fb = dev.node_features.device_arrays()
  assert set(fa) == set(fb)
  for k in fa:
    x, y = np.asarray(fa[k]), np.asarray(fb[k])
    assert x.dtype == y.dtype and x.shape == y.shape, k
    assert x.tobytes() == y.tobytes(), k        # -0.0 included
    assert fa[k].sharding.is_equivalent_to(fb[k].sharding, x.ndim), k
  assert dev.node_features.cache_rows == host.node_features.cache_rows == 24
  assert np.array_equal(dev.node_features.cache_ids,
                        host.node_features.cache_ids)
  assert dev.node_features.feats is None and dev.graph.indices is None
  # the indexes a device built are the host's (their starts were compared
  # with the tables above); the label store shares the rows' index
  for name in ('_row_index', '_cache_index'):
    a, b = getattr(host.node_features, name), getattr(dev.node_features,
                                                      name)
    assert (a.shift, a.depth) == (b.shift, b.depth), name
  assert dev.node_labels._row_index is dev.node_features._row_index
  assert (dev.node_labels.device_arrays()['feat_starts']
          is fb['feat_starts'])


def test_device_built_store_answers_like_the_host_built(world):
  host, dev = world['host'].node_features, world['dev'].node_features
  rng = np.random.default_rng(2)
  ids = rng.integers(0, N, (P, 20)).astype(np.int32)
  ids[:, -2:] = -1
  want = np.where((ids >= 0)[..., None], world['feat'][np.maximum(ids, 0)],
                  0)
  for store in (host, dev):
    assert np.asarray(store.get(ids)).tobytes() == want.tobytes()
    store.reset_stats()
  lab = np.asarray(world['dev'].node_labels.get(ids))[..., 0]
  assert np.array_equal(lab, np.where(ids >= 0,
                                      world['label'][np.maximum(ids, 0)], 0))
  world['dev'].node_labels.reset_stats()


@pytest.mark.parametrize('hotness', ['host', 'none'])
def test_cache_selection_by_host_vector_or_none(world, hotness):
  mesh, host = world['mesh'], world['host']
  fa = host.node_features.device_arrays()
  hot = world['hot'] if hotness == 'host' else None
  want = glt.distributed.DistFeature(
      P, [(np.nonzero(world['node_pb'] == q)[0],
           world['feat'][world['node_pb'] == q]) for q in range(P)],
      world['node_pb'], mesh, cache_rows=17, hotness=hot).device_arrays()
  got = glt.distributed.DistFeature.from_device_shards(
      mesh, world['node_pb'], fa['feat_ids'], fa['feats'], cache_rows=17,
      hotness=hot).device_arrays()
  for k in ('cache_ids', 'cache_feats'):
    assert np.asarray(want[k]).tobytes() == np.asarray(got[k]).tobytes(), k


def test_cpu_get_and_refusals(world):
  import jax
  mesh, dev = world['mesh'], world['dev']
  ids = np.array([0, 5, N - 1, 17, 17])
  assert np.array_equal(dev.node_features.cpu_get(ids), world['feat'][ids])
  with pytest.raises(ValueError, match='host copy'):
    dev.graph.sorted_local_indices()
  fa = dev.node_features.device_arrays()
  whole = jax.device_put(np.asarray(fa['feat_ids']), jax.devices()[0])
  with pytest.raises(ValueError, match='not sharded'):
    glt.distributed.DistFeature.from_device_shards(
        mesh, world['node_pb'], whole, fa['feats'])
  ga = dev.graph.device_arrays(mesh)
  bare = glt.distributed.DistGraph.from_device_shards(
      mesh, world['node_pb'], ga['row_ids'], ga['indptr'], ga['indices'])
  assert bare.device_arrays(mesh)['eids'].shape == (P, 1)
  with pytest.raises(ValueError, match='edge ids'):
    glt.distributed.DistNeighborSampler(bare, [2], mesh, with_edge=True)
  # without edge ids the sampler draws the same neighbours
  seeds = np.arange(P * 4).reshape(P, 4)
  one = glt.distributed.DistNeighborSampler(bare, [3, 2], mesh, seed=3)
  two = glt.distributed.DistNeighborSampler(world['host'].graph, [3, 2],
                                            mesh, seed=3)
  a, b = one.sample_from_nodes(seeds), two.sample_from_nodes(seeds)
  assert np.array_equal(np.asarray(a.node), np.asarray(b.node))
  assert np.array_equal(np.asarray(a.row), np.asarray(b.row))


def _loader(ds, mesh, caps=None, **kw):
  return glt.distributed.DistNeighborLoader(
      ds, [3, 2], np.arange(0, N, 2), batch_size=6, shuffle=False,
      drop_last=True, seed=0, mesh=mesh, dedup='merge', frontier_caps=caps,
      seed_labels_only=True, **kw)


def test_loader_batches_equal_over_both_builds(world):
  mesh = world['mesh']
  for a, b in zip(_loader(world['host'], mesh), _loader(world['dev'], mesh)):
    for k in ('node', 'x', 'y', 'edge_index', 'edge_mask',
              'num_sampled_nodes'):
      x, y = np.asarray(getattr(a, k)), np.asarray(getattr(b, k))
      assert x.tobytes() == y.tobytes(), k
  gc.collect()


def _state(model, loader, tx):
  import jax
  import jax.numpy as jnp
  first = next(iter(loader))
  params = model.init(jax.random.PRNGKey(0), np.asarray(first.x)[0],
                      np.asarray(first.edge_index)[0],
                      np.asarray(first.edge_mask)[0])
  return train_lib.TrainState(params, tx.init(params), jnp.int32(0))


def test_scanned_epoch_over_device_shards_equals_per_step_over_host_build(
    world):
  """The acceptance of the constructors: ``DistScanTrainer`` over the
  device-built dataset under mesh-probed caps trains what the per-step
  ``DistNeighborLoader`` loop trains over the host-built one — losses and
  parameters bit for bit on XLA:CPU — without overflow, and publishes
  what its exchanges carried."""
  import jax
  import optax
  mesh = world['mesh']
  caps = glt.sampler.estimate_dist_frontier_caps(
      world['dev'].graph, mesh, [3, 2], 6, input_nodes=np.arange(0, N, 2),
      num_probes=4, slack=1.5, seed=0, multiple=8)
  assert len(caps) == 2 and all(c % 8 == 0 for c in caps)
  assert caps[0] <= 6 * 3 * 1.5 + 8
  model = glt.models.GraphSAGE(hidden_dim=8, out_dim=5, num_layers=2)
  tx = optax.adam(1e-2)
  ref = glt.loader.DistFusedEpochTrainer(
      _loader(world['host'], mesh, caps), model, tx, 5)
  state_ref = _state(model, _loader(world['host'], mesh, caps), tx)
  scan_loader = _loader(world['dev'], mesh, caps)
  trainer = glt.loader.DistScanTrainer(scan_loader, model, tx, 5,
                                       chunk_size=4)
  state = _state(model, _loader(world['dev'], mesh, caps), tx)
  gc.collect()
  glt.utils.trace.reset_counters('dist_feature')
  glt.utils.trace.reset_counters('dist_exchange')
  state_ref, losses_ref = ref.run_epoch_steps(state_ref)
  stats_ref = glt.utils.trace.counters('dist_feature')
  glt.utils.trace.reset_counters('dist_feature')
  state, losses, _ = trainer.run_epoch(state)
  assert not scan_loader.check_overflow()
  np.testing.assert_array_equal(
      np.asarray(losses), np.asarray([np.asarray(x) for x in losses_ref]))
  for a, b in zip(jax.tree.leaves(state_ref.params),
                  jax.tree.leaves(state.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  assert glt.utils.trace.counters('dist_feature') == stats_ref
  # rows the hops' exchanges sent: every frontier id another shard owns,
  # counted from the per-step loader's own batches
  steps = len(losses_ref)
  want = np.zeros(2, np.int64)
  for batch in _loader(world['host'], mesh, caps):
    node = np.asarray(batch.node)
    nsn = np.asarray(batch.num_sampled_nodes)
    for p in range(P):
      lo = 0
      for h in range(2):
        front = node[p, lo:lo + nsn[p, h]]
        want[h] += int((world['node_pb'][front] != p).sum())
        lo += nsn[p, h]
  got = glt.utils.trace.counters('dist_exchange')
  assert [got[f'dist_exchange.rows.hop{h}'] for h in range(2)] == \
      want.tolist()
  gc.collect()


def test_exchange_rows_cover_the_chunks_run(world):
  """A truncated epoch publishes the rows of the chunks it ran and no
  more: ``run_epoch(max_steps=2)`` at chunk 2 counts the first two steps
  of the per-step loader's stream, and the next epoch starts from 0."""
  import optax
  mesh = world['mesh']
  model = glt.models.GraphSAGE(hidden_dim=8, out_dim=5, num_layers=2)
  tx = optax.adam(1e-2)
  trainer = glt.loader.DistScanTrainer(_loader(world['dev'], mesh), model,
                                       tx, 5, chunk_size=2)
  state = _state(model, _loader(world['dev'], mesh), tx)
  want = np.zeros(2, np.int64)
  for step, batch in enumerate(_loader(world['host'], mesh)):
    if step == 2:
      break
    node = np.asarray(batch.node)
    nsn = np.asarray(batch.num_sampled_nodes)
    for p in range(P):
      lo = 0
      for h in range(2):
        front = node[p, lo:lo + nsn[p, h]]
        want[h] += int((world['node_pb'][front] != p).sum())
        lo += nsn[p, h]
  glt.utils.trace.reset_counters('dist_exchange')
  trainer.run_epoch(state, max_steps=2)
  got = glt.utils.trace.counters('dist_exchange')
  assert got == {f'dist_exchange.rows.hop{h}': int(want[h])
                 for h in range(2)}
  assert trainer._sent == []
  gc.collect()


def test_the_collectives_have_scopes_of_their_own(world):
  """``glt.sample/hop<h>/exchange`` apart from ``/draw``,
  ``glt.collate/cache`` and ``/exchange``, ``glt.train/allreduce``: in the
  lowered chunk's op names, each holding the ops it says it holds."""
  import re

  import jax
  import optax
  mesh = world['mesh']
  model = glt.models.GraphSAGE(hidden_dim=8, out_dim=5, num_layers=2)
  tx = optax.adam(1e-2)
  loader = _loader(world['dev'], mesh)
  trainer = glt.loader.DistScanTrainer(loader, model, tx, 5, chunk_size=2)
  state = _state(model, _loader(world['dev'], mesh), tx)
  seen = {}
  chunk = trainer._chunk_fn_for(2)
  raw = getattr(chunk, '_glt_instrumented', chunk)

  def spy(*args):
    seen['text'] = raw.lower(*args).as_text(debug_info=True)
    return raw(*args)

  trainer._chunk_fns[2] = spy
  trainer.run_epoch(state, max_steps=2)
  text = seen['text']
  names = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))

  def scopes_of(op):
    out = set()
    for ref in re.findall(rf'stablehlo\.{op}.*? loc\((#loc\d+)\)', text):
      out.add(names.get(ref, ''))
    return out

  # a bucket that may overflow runs under lax.cond, which puts its own
  # components into the path: glt.sample/cond/branch_1_fun/hop1/exchange
  plain = lambda s: re.sub(r'/(cond|branch_\d+_fun)(?=/)', '', s)
  a2a = {plain(s) for s in scopes_of('all_to_all')}
  assert a2a, 'no all_to_all found in the lowered chunk'
  for h in range(2):
    assert any(f'glt.sample/hop{h}/exchange' in s for s in a2a), a2a
  assert any('glt.collate/exchange' in s for s in a2a)
  assert not any('/draw' in s for s in a2a)
  assert any('glt.collate/cache' in v for v in names.values())
  assert any('hop1/draw' in v and v.startswith('glt.sample')
             for v in names.values())
  # the pmean is a psum (a region op: its location follows the region)
  psums = {v for v in names.values() if v.endswith('/psum')}
  assert any('glt.train/allreduce' in s for s in psums), psums
  assert not any('glt.train/fwd_bwd' in s for s in psums)
  gc.collect()
