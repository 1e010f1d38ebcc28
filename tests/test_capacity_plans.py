"""CapacityPlan: typed per-ntype/etype closed shapes on every marquee
fast path (sampler/capacity.py, docs/capacity_plans.md).

The contracts under test, in order:

* **The plan artifact** — the homo degenerate plan (one ntype, one
  implicit etype, stride 1) and the typed hetero plan agree with the
  engine kernels they wrap: hop/node/edge caps, per-(hop, etype) PRNG
  draw counts and the closed frame key set. ``CapacityPlanError``
  names the consumer, the missing input, and the doc anchor.
* **Link ack provenance** — LINK block frames carry the seed edge
  endpoints (``#META.edge_batch``) with the true pre-pad count, read
  back through ``sampler.ack_edge_ids``; node frames return None.
* **Hetero remote** — RemoteScanTrainer on typed seeds is bit-identical
  to the per-batch remote path (losses AND params, two epochs) within
  the ceil(steps/K)+2 dispatch budget under GLT_STRICT, and a crash at
  a chunk boundary resumes bit-identically in a fresh trainer.
* **Hetero tiered** — TieredDistScanTrainer on per-ntype stores matches
  the non-tiered DistScanTrainer bitwise at the same budget; per-ntype
  stores sharing one spill_dir are refused at construction (their
  part_NNN spill files would silently overwrite); crash + resume is
  bit-identical.
"""
import tempfile

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.models import train as train_lib
from graphlearn_tpu.sampler import CapacityPlanError
from graphlearn_tpu.sampler.capacity import (DEFAULT_NTYPE, CapacityPlan,
                                             ack_edge_ids)
from graphlearn_tpu.storage import TieredDistFeature, TieredDistScanTrainer
from graphlearn_tpu.typing import GraphPartitionData, reverse_edge_type
from graphlearn_tpu.utils import faults, trace

# ---- remote hetero fixture (user--buys--item bipartite ring) ----
UB, BU = ('user', 'buys', 'item'), ('item', 'rev_buys', 'user')
NU, NI = 18, 12
BS, K, CLASSES = 4, 2, 3
FANOUTS = {UB: [2, 2], BU: [2, 2]}

# ---- tiered hetero fixture (u/v ring over 2 partitions) ----
TN = 40
NUM_PARTS = 2
HOT = 4
ET1, ET2 = ('u', 'to', 'v'), ('v', 'back', 'u')
T_FANOUTS = {ET1: [2, 2], ET2: [1, 1]}


@pytest.fixture(autouse=True)
def _clean():
  faults.disarm()
  trace.reset_counters()
  yield
  faults.disarm()
  trace.reset_counters()
  from graphlearn_tpu.distributed import dist_client
  if dist_client._client is not None:
    dist_client._client.close()
    dist_client._client = None


# --------------------------------------------------------- plan artifact


class TestCapacityPlanUnit:

  def test_homo_degenerate_plan(self):
    plan = CapacityPlan.homo(8, [2, 2])
    assert plan.ntypes == (DEFAULT_NTYPE,)
    assert not plan.is_hetero
    assert plan.batch_cap == 8
    assert plan.num_hops == 2
    # stride 1: the homo stream's implicit counter advance falls out
    assert plan.key_draws_per_batch == 1
    # one implicit etype per hop, caps from the homo capacity chain
    from graphlearn_tpu.sampler.neighbor_sampler import capacity_plan
    caps = capacity_plan(8, (2, 2))
    assert plan.node_caps[DEFAULT_NTYPE] == sum(caps)
    (h0,), (h1,) = (list(p.values()) for p in plan.hop_caps)
    assert h0 == (int(caps[0]), 2, int(caps[1]))
    assert h1 == (int(caps[1]), 2, int(caps[2]))
    # homo frame keys: the untyped flat SampleMessage convention
    assert plan.frame_keys()[:2] == ['node', 'num_nodes']

  def test_hetero_plan_typed_shapes(self):
    plan = CapacityPlan.hetero([UB, BU], FANOUTS, {'user': BS}, 'out',
                               input_type='user')
    assert plan.is_hetero
    assert set(plan.ntypes) == {'user', 'item'}
    assert plan.input_type == 'user' and plan.batch_cap == BS
    # one PRNG draw per (hop, etype) touch — the counter stride typed
    # block producers multiply batch indices by
    assert plan.key_draws_per_batch == \
        sum(len(per_et) for per_et in plan.hop_caps)
    assert plan.key_draws_per_batch >= 2
    # out edge_dir: engines emit blocks under the REVERSED etype, one
    # fcap*k contribution per (hop, etype) touch
    assert set(plan.edge_caps) == set(plan.out_etypes())
    for oet, cap in plan.edge_caps.items():
      et = reverse_edge_type(oet)
      assert cap == sum(per_et[et][0] * per_et[et][1]
                        for per_et in plan.hop_caps if et in per_et)
    # the closed typed frame key set carries per-ntype and per-etype
    # dotted keys plus the typed meta
    keys = plan.frame_keys()
    assert '#META.hetero' in keys and 'x.user' in keys and \
        'x.item' in keys
    assert any(k.startswith('row.') for k in keys)
    assert 'batch.user' in keys and 'y.user' in keys

  def test_from_sampler_requires_input_type(self):
    ds = make_hetero_dataset()
    sampler = glt.sampler.NeighborSampler(ds.graph, FANOUTS)
    with pytest.raises(CapacityPlanError) as ei:
      CapacityPlan.from_sampler(sampler, BS)
    # the typed error names consumer, missing input and the doc anchor
    assert ei.value.consumer == 'CapacityPlan.from_sampler'
    assert 'docs/capacity_plans.md' in str(ei.value)
    plan = CapacityPlan.from_sampler(sampler, BS, input_type='user')
    assert plan.is_hetero and plan.input_type == 'user'

  def test_error_is_a_value_error(self):
    # call sites that used to catch the bare ValueError guards keep
    # working — CapacityPlanError subtypes it
    err = CapacityPlanError('Consumer', 'thing is missing', hint='do X')
    assert isinstance(err, ValueError)
    assert 'Consumer' in str(err) and 'do X' in str(err)


# --------------------------------------------------- link ack provenance


def make_homo_dataset(n=NU):
  rows = np.concatenate([np.arange(n), np.arange(n)])
  cols = np.concatenate([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n])
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=n)
  feat = np.arange(n, dtype=np.float32)[:, None] * np.ones((1, 4),
                                                           np.float32)
  ds.init_node_features(feat)
  ds.init_node_labels(np.arange(n) % CLASSES)
  return ds


def test_link_block_frames_carry_edge_batch_provenance():
  """LINK block frames ship the seed EDGE endpoints each batch covered
  (with the true pre-pad count for the cyclically padded tail), so a
  failover replay can account every seed edge exactly once — the link
  counterpart of the node frames' 'batch' record."""
  from graphlearn_tpu.distributed.block_producer import \
      BlockSampleProducer
  from graphlearn_tpu.sampler import (EdgeSamplerInput, NegativeSampling,
                                      SamplingConfig, SamplingType)
  ds = make_homo_dataset()
  n_edges = 10
  rows = np.arange(n_edges)
  cols = (np.arange(n_edges) + 1) % NU
  cfg = SamplingConfig(SamplingType.LINK, [2, 2], BS, False, False,
                       False, True, True, False, 'out', 0)
  bp = BlockSampleProducer(
      ds, EdgeSamplerInput(rows, cols,
                           neg_sampling=NegativeSampling('binary', 1)),
      cfg)
  # 10 edges / bs 4 -> 3 batches, ragged tail of 2
  assert bp.num_batches() == 3
  frame = bp.build_frame(0, 0, 3)
  assert '#META.edge_batch' in frame and \
      '#META.edge_batch_size' in frame
  for j in range(3):
    got = ack_edge_ids(frame, j)
    true_n = min(BS, n_edges - j * BS)
    assert got.shape == (2, true_n)
    np.testing.assert_array_equal(got[0], rows[j * BS:j * BS + true_n])
    np.testing.assert_array_equal(got[1], cols[j * BS:j * BS + true_n])
  # node frames carry no edge provenance: ack_edge_ids returns None
  node_cfg = SamplingConfig(SamplingType.NODE, [2, 2], BS, False, False,
                            False, True, False, False, 'out', 0)
  node_bp = BlockSampleProducer(ds, np.arange(NU), node_cfg)
  assert ack_edge_ids(node_bp.build_frame(0, 0, 2), 0) is None


# -------------------------------------------------------- hetero remote


def make_hetero_dataset():
  u = np.arange(NU)
  rows = np.concatenate([u, u])
  cols = np.concatenate([u % NI, (u + 1) % NI])
  ub = np.stack([rows, cols])
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph({UB: ub, BU: ub[::-1].copy()}, graph_mode='CPU',
                num_nodes={UB: NU, BU: NI})
  ds.init_node_features(
      {'user': np.arange(NU, dtype=np.float32)[:, None] *
       np.ones((1, 3), np.float32),
       'item': 100.0 + np.arange(NI, dtype=np.float32)[:, None] *
       np.ones((1, 3), np.float32)})
  ds.init_node_labels({'user': np.arange(NU) % CLASSES})
  return ds


def _start_block_server(ds):
  from graphlearn_tpu.distributed.dist_server import DistServer
  from graphlearn_tpu.distributed.rpc import RpcServer
  s = DistServer(ds)
  rpc = RpcServer(handlers={
      'create_sampling_producer': s.create_sampling_producer,
      'producer_num_expected': s.producer_num_expected,
      'start_new_epoch_sampling': s.start_new_epoch_sampling,
      'fetch_one_sampled_message': s.fetch_one_sampled_message,
      'destroy_sampling_producer': s.destroy_sampling_producer,
      'create_block_producer': s.create_block_producer,
      'block_producer_num_batches': s.block_producer_num_batches,
      'block_produce': s.block_produce,
      'block_fetch': s.block_fetch,
      'destroy_block_producer': s.destroy_block_producer,
      'get_dataset_meta': s.get_dataset_meta,
      'heartbeat': s.heartbeat,
      'get_metrics': s.get_metrics,
      'exit': s.exit,
  })
  return s, rpc


def _init_client(pairs):
  from graphlearn_tpu.distributed import dist_client
  dist_client.init_client(
      num_servers=len(pairs), num_clients=1, client_rank=0,
      server_addrs=[(rpc.host, rpc.port) for _, rpc in pairs])


def _teardown(pairs):
  from graphlearn_tpu.distributed import dist_client
  if dist_client._client is not None:
    dist_client._client.close()
    dist_client._client = None
  for s, rpc in pairs:
    s.exit()
    rpc.shutdown()


def hetero_batch_to_dict(b, t_in):
  nsn = np.asarray(b.num_sampled_nodes[t_in]).reshape(-1)
  return dict(x={t: v for t, v in b.x.items()},
              edge_index=dict(b.edge_index),
              edge_mask=dict(b.edge_mask),
              y=b.y[t_in],
              num_seed_nodes=nsn[0])


def _rgnn_model_state(ds, seeds, key=0):
  import jax
  model = glt.models.RGNN(etypes=(reverse_edge_type(UB),
                                  reverse_edge_type(BU)),
                          hidden_dim=8, out_dim=CLASSES, num_layers=2,
                          out_ntype='user')
  import optax
  tx = optax.adam(1e-2)
  local = glt.loader.NeighborLoader(ds, FANOUTS, ('user', seeds),
                                    batch_size=BS, shuffle=False)
  template = hetero_batch_to_dict(next(iter(local)), 'user')
  state, tx = train_lib.create_train_state(
      model, jax.random.PRNGKey(key), template, optimizer=tx)
  return model, tx, state, template


def _make_hetero_trainer(model, tx, seeds, **kw):
  opts = kw.pop('worker_options', None) or \
      glt.distributed.RemoteDistSamplingWorkerOptions(server_rank=0)
  kw.setdefault('batch_size', BS)
  kw.setdefault('chunk_size', K)
  kw.setdefault('seed', 0)
  return glt.distributed.RemoteScanTrainer(
      FANOUTS, ('user', seeds), model, tx, CLASSES,
      worker_options=opts, **kw)


def test_hetero_remote_scan_bit_identity_and_budget():
  """The hetero acceptance gate: typed seeds select typed block
  streams, and the chunk-staged epoch equals the per-batch remote
  hetero path bit-for-bit (losses AND params, two epochs — the typed
  counter stride makes the streams the same) within the homo path's
  ceil(steps/K)+2 dispatch budget under GLT_STRICT."""
  import jax
  ds = make_hetero_dataset()
  seeds = np.arange(NU)
  pairs = [_start_block_server(ds)]
  try:
    _init_client(pairs)
    model, tx, state_ref, template = _rgnn_model_state(ds, seeds)

    # per-batch remote reference (1 worker / prefetch 1: the only
    # deterministically-ordered per-batch configuration)
    opts = glt.distributed.RemoteDistSamplingWorkerOptions(
        server_rank=0, num_workers=1, prefetch_size=1)
    loader = glt.distributed.RemoteDistNeighborLoader(
        FANOUTS, ('user', seeds), batch_size=BS, collect_features=True,
        worker_options=opts, seed=0)
    assert len(loader) == 5
    step, _ = train_lib.make_train_step(model, tx, CLASSES)
    losses_ref = [[], []]
    for e in range(2):
      for b in loader:
        state_ref, loss, _ = step(state_ref,
                                  hetero_batch_to_dict(b, 'user'))
        losses_ref[e].append(np.asarray(loss))
      assert len(losses_ref[e]) == 5
    loader.shutdown()

    trainer = _make_hetero_trainer(model, tx, seeds)
    state_scan, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), template, optimizer=tx)
    steps = len(trainer)
    assert steps == 5
    for e in range(2):
      with glt.utils.count_dispatches() as dc:
        state_scan, losses, accs = trainer.run_epoch(state_scan)
      total = (dc.counts.get('remote_epoch_begin', 0) +
               dc.counts.get('remote_scan_chunk', 0) +
               dc.counts.get('remote_metrics_concat', 0))
      assert total == -(-steps // K) + 2, dc.counts
      np.testing.assert_array_equal(
          np.asarray(losses), np.asarray(losses_ref[e]).reshape(-1))
      assert sorted(trainer.last_epoch_seed_ids.tolist()) == \
          list(range(NU))
    for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                    jax.tree_util.tree_leaves(state_scan.params)):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    trainer.shutdown()
  finally:
    _teardown(pairs)


def test_hetero_remote_crash_resume_bit_identical(tmp_path):
  """ChunkCheckpointer rides the hetero ack_hook seam unchanged: a
  crash at chunk 2 of the typed stream resumes in a FRESH trainer from
  the block boundary, bit-identical to the uninterrupted run (typed
  blocks are counter-addressed with the plan-derived stride)."""
  import jax

  from graphlearn_tpu.recovery import ChunkCheckpointer
  ds = make_hetero_dataset()
  seeds = np.arange(NU)
  pairs = [_start_block_server(ds)]
  try:
    _init_client(pairs)
    model, tx, state_a, template = _rgnn_model_state(ds, seeds)

    ref = _make_hetero_trainer(model, tx, seeds)
    state_a, losses_ref, accs_ref = ref.run_epoch(state_a)
    ref.shutdown()

    ckdir = str(tmp_path / 'ck')
    victim = _make_hetero_trainer(model, tx, seeds)
    ck = ChunkCheckpointer(ckdir, every=1).attach(victim)

    def crash(c, start, k):
      if c == 2:
        raise RuntimeError('injected mid-epoch crash')

    victim.stage_hook = crash
    state_b, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), template, optimizer=tx)
    with pytest.raises(RuntimeError, match='injected'):
      victim.run_epoch(state_b)
    ck.close()
    victim.shutdown()

    fresh = _make_hetero_trainer(model, tx, seeds)
    tmpl_state, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(7), template, optimizer=tx)
    state_c, losses, accs = ChunkCheckpointer(ckdir).resume_epoch(
        fresh, tmpl_state)
    np.testing.assert_array_equal(np.asarray(losses),
                                  np.asarray(losses_ref))
    np.testing.assert_array_equal(np.asarray(accs),
                                  np.asarray(accs_ref))
    for a, b in zip(jax.tree_util.tree_leaves(state_a.params),
                    jax.tree_util.tree_leaves(state_c.params)):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fresh._epochs == 1
    fresh.shutdown()
  finally:
    _teardown(pairs)


# -------------------------------------------------------- hetero tiered


def tiered_fixture():
  r1 = np.concatenate([np.arange(TN), np.arange(TN)])
  c1 = np.concatenate([np.arange(TN), (np.arange(TN) + 1) % TN])
  r2 = np.arange(TN)
  c2 = (np.arange(TN) + 2) % TN
  pb_u = (np.arange(TN) % NUM_PARTS).astype(np.int32)
  pb_v = ((np.arange(TN) + 1) % NUM_PARTS).astype(np.int32)
  parts = []
  for p in range(NUM_PARTS):
    part = {}
    m1 = pb_u[r1] == p
    part[ET1] = GraphPartitionData(
        edge_index=np.stack([r1[m1], c1[m1]]),
        eids=np.arange(2 * TN)[m1])
    m2 = pb_v[r2] == p
    part[ET2] = GraphPartitionData(
        edge_index=np.stack([r2[m2], c2[m2]]), eids=np.arange(TN)[m2])
    parts.append(part)
  node_pb = {'u': pb_u, 'v': pb_v}
  feats = {t: [(np.nonzero(node_pb[t] == p)[0],
                np.nonzero(node_pb[t] == p)[0][:, None].astype(
                    np.float32) * np.ones((1, 4), np.float32))
               for p in range(NUM_PARTS)] for t in ('u', 'v')}
  return parts, feats, node_pb


def _mesh():
  import jax
  from jax.sharding import Mesh
  return Mesh(np.array(jax.devices()[:NUM_PARTS]), ('g',))


def make_tiered_loader(tiered, spill_dir=None, shared_spill=False):
  import os
  parts, feats, node_pb = tiered_fixture()
  mesh = _mesh()
  dg = glt.distributed.DistHeteroGraph(NUM_PARTS, 0, parts, node_pb)
  if tiered:
    sub = (lambda t: spill_dir) if shared_spill else \
        (lambda t: os.path.join(spill_dir, t))
    df = {t: TieredDistFeature(NUM_PARTS, feats[t], node_pb[t],
                               mesh=mesh, spill_dir=sub(t),
                               hot_prefix_rows=HOT, split_ratio=0.25)
          for t in ('u', 'v')}
  else:
    df = {t: glt.distributed.DistFeature(NUM_PARTS, feats[t],
                                         node_pb[t], mesh,
                                         split_ratio=0.25)
          for t in ('u', 'v')}
  ds = glt.distributed.DistDataset(
      NUM_PARTS, 0, dg, df,
      node_labels={'u': np.arange(TN) % 3, 'v': np.arange(TN) % 3})
  return glt.distributed.DistNeighborLoader(
      ds, T_FANOUTS, ('u', np.arange(14)),
      batch_size=2, shuffle=False, drop_last=False, seed=0, mesh=mesh)


def _tiered_model_tx():
  import optax
  model = glt.models.RGNN(
      etypes=(reverse_edge_type(ET1), reverse_edge_type(ET2)),
      hidden_dim=8, out_dim=3, num_layers=2, out_ntype='u')
  return model, optax.adam(1e-2)


def _tiered_state(model, loader, tx):
  import jax
  import jax.numpy as jnp
  first = next(iter(loader))
  one = lambda d: {k: np.asarray(v)[0] for k, v in d.items()}
  params = model.init(jax.random.PRNGKey(0), one(first.x),
                      one(first.edge_index), one(first.edge_mask))
  return train_lib.TrainState(params, tx.init(params), jnp.int32(0))


def test_hetero_tiered_bit_identity_and_budget():
  """TieredDistScanTrainer accepts per-ntype TieredDistFeature stores
  (the CapacityPlan threads per-ntype exchange slabs through the
  stagers): epochs bit-identical to the non-tiered DistScanTrainer at
  the ceil(steps/K)+2 budget, with one ExchangePlan per ntype."""
  import jax
  model, tx = _tiered_model_tx()
  ref = glt.loader.DistScanTrainer(make_tiered_loader(False), model,
                                   tx, 3, chunk_size=2)
  state_ref = _tiered_state(model, make_tiered_loader(False), tx)
  ref_losses = []
  for _ in range(2):
    state_ref, losses, _ = ref.run_epoch(state_ref)
    ref_losses.append(np.asarray(losses))

  tmp = tempfile.mkdtemp(prefix='glt_hetero_tiered_')
  trainer = TieredDistScanTrainer(make_tiered_loader(True, spill_dir=tmp),
                                  model, tx, 3, chunk_size=2)
  state = _tiered_state(model, make_tiered_loader(False), tx)
  with glt.utils.count_dispatches() as dc:
    state, l1, _ = trainer.run_epoch(state)
  assert dc.total <= -(-4 // 2) + 2, dc.counts
  np.testing.assert_array_equal(np.asarray(l1), ref_losses[0])
  state, l2, _ = trainer.run_epoch(state)
  np.testing.assert_array_equal(np.asarray(l2), ref_losses[1])
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # one exchange plan per ntype, all of them actually planning rows
  assert isinstance(trainer.last_plan, dict) and \
      set(trainer.last_plan) == {'u', 'v'}
  for t, p in trainer.last_plan.items():
    assert p.stats()['planned_rows'] > 0, (t, p.stats())
  trainer.close()


def test_hetero_tiered_shared_spill_dir_refused():
  """Two per-ntype stores sharing one spill_dir would silently
  overwrite each other's part_NNN spill files — the CapacityPlanError
  names the clash at construction, before any epoch runs."""
  tmp = tempfile.mkdtemp(prefix='glt_spill_clash_')
  model, tx = _tiered_model_tx()
  with pytest.raises(CapacityPlanError) as ei:
    TieredDistScanTrainer(
        make_tiered_loader(True, spill_dir=tmp, shared_spill=True),
        model, tx, 3, chunk_size=2)
  msg = str(ei.value)
  assert 'spill_dir' in msg and 'docs/capacity_plans.md' in msg


@pytest.mark.slow  # tier-1 budget (PR 19): tiered variant — the remote
# hetero crash-resume rep stays tier-1, and the homo tiered crash-resume
# is already slow (PR 17); full suite runs this
def test_hetero_tiered_crash_resume_bit_identical(tmp_path):
  """TieredDistScanTrainer hetero crash at a chunk boundary resumes
  bit-identically in a fresh trainer over fresh per-ntype stores."""
  import jax

  from graphlearn_tpu.recovery import ChunkCheckpointer
  model, tx = _tiered_model_tx()
  tmp = tempfile.mkdtemp(prefix='glt_hetero_tiered_ref_')
  ref = TieredDistScanTrainer(make_tiered_loader(True, spill_dir=tmp),
                              model, tx, 3, chunk_size=2)
  state_a = _tiered_state(model, make_tiered_loader(False), tx)
  state_a, losses_ref, _ = ref.run_epoch(state_a)
  ref.close()

  ckdir = str(tmp_path / 'ck')
  tmp_v = tempfile.mkdtemp(prefix='glt_hetero_tiered_v_')
  victim = TieredDistScanTrainer(
      make_tiered_loader(True, spill_dir=tmp_v), model, tx, 3,
      chunk_size=2)
  ck = ChunkCheckpointer(ckdir, every=1).attach(victim)

  def crash(c, start, k):
    if c == 1:
      raise RuntimeError('injected mid-epoch crash')

  victim.stage_hook = crash
  state_v = _tiered_state(model, make_tiered_loader(False), tx)
  template = _tiered_state(model, make_tiered_loader(False), tx)
  with pytest.raises(RuntimeError, match='injected'):
    victim.run_epoch(state_v)
  ck.close()
  victim.close()

  tmp_f = tempfile.mkdtemp(prefix='glt_hetero_tiered_f_')
  fresh = TieredDistScanTrainer(
      make_tiered_loader(True, spill_dir=tmp_f), model, tx, 3,
      chunk_size=2)
  state_c, losses, _ = ChunkCheckpointer(ckdir).resume_epoch(
      fresh, template)
  np.testing.assert_array_equal(np.asarray(losses),
                                np.asarray(losses_ref))
  for a, b in zip(jax.tree_util.tree_leaves(state_a.params),
                  jax.tree_util.tree_leaves(state_c.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  assert fresh._epochs == 1
  fresh.close()
