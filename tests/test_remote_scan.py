"""RemoteScanTrainer: chunk-staged remote epochs (docs/remote_scan.md).

The contracts under test, in order:

* **Bit-identity** — with shuffle=False, one server and
  ``wire_dtype=None``, the chunk-staged epoch's losses and final params
  equal the per-batch remote path's EXACTLY, including a ragged tail
  batch, a tail chunk, and the epoch-2 stream continuation (the server
  block stream is the per-batch mp-worker stream, counter-addressed).
* **Dispatch budget** — ``ceil(steps/K) + 2`` instrumented client
  dispatches per epoch under GLT_STRICT (this module runs strict by
  default — tests/conftest.py).
* **Degrade-to-sync** — an armed ``remote.block_fetch`` fault moves the
  same block fetch onto the dispatch thread; the epoch completes
  bit-identically (``remote.prefetch_miss`` counts the degradation).
* **Chunk-granular failover** — a dead server's pending blocks are
  re-replayed by survivors from the same counter stream: exact seed
  coverage, bit-identical losses, orphan-free span tree.
* **Crash + resume** — ``recovery.ChunkCheckpointer`` rides the
  ack_hook seam unchanged; a kill at a block boundary resumes
  bit-identically in a fresh trainer.
"""
import multiprocessing as mp
import os
import signal
import time

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.models import GraphSAGE, train as train_lib
from graphlearn_tpu.utils import faults, trace

N = 38          # 38 seeds / bs 4 -> 10 batches, ragged tail batch of 2
BS = 4
K = 4           # 10 steps at K=4 -> chunks of 4, 4 and a tail chunk of 2
CLASSES = 3
FANOUTS = [2, 2]


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


def make_dataset(n=N):
  rows = np.concatenate([np.arange(n), np.arange(n)])
  cols = np.concatenate([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n])
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=n)
  feat = np.arange(n, dtype=np.float32)[:, None] * np.ones((1, 4),
                                                           np.float32)
  ds.init_node_features(feat)
  ds.init_node_labels(np.arange(n) % CLASSES)
  return ds


def _start_block_server(ds):
  """DistServer + RpcServer in THIS process (the chaos-suite pattern):
  fast, and fault sites arm deterministically."""
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


def _template_batch(ds, seeds):
  """Model-init template from a LOCAL loader (same batch_cap/fanouts
  as the server streams, so shapes match) — nothing remote consumed."""
  loader = glt.loader.NeighborLoader(ds, FANOUTS, seeds, batch_size=BS,
                                     shuffle=False)
  return train_lib.batch_to_dict(next(iter(loader)))


def _model_and_state(ds, seeds, key=0):
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  template = _template_batch(ds, seeds)
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(key),
                                           template)
  return model, tx, state, template


def _make_trainer(model, tx, seeds, **kw):
  opts = kw.pop('worker_options', None) or \
      glt.distributed.RemoteDistSamplingWorkerOptions(server_rank=0)
  kw.setdefault('batch_size', BS)
  kw.setdefault('chunk_size', K)
  kw.setdefault('seed', 0)
  return glt.distributed.RemoteScanTrainer(
      FANOUTS, seeds, model, tx, CLASSES, worker_options=opts, **kw)


# -------------------------------------------------------- bit-identity


def test_remote_scan_bit_identity_vs_per_batch():
  """The acceptance gate: chunk-staged epoch == per-batch remote epoch
  bit-for-bit (losses AND params), across two epochs (counter-stream
  continuation), with a ragged tail batch and a tail chunk. Seed
  coverage is exact per epoch (the chunk-granular ack record)."""
  import jax
  ds = make_dataset()
  seeds = np.arange(N)
  pairs = [_start_block_server(ds)]
  try:
    _init_client(pairs)
    model, tx, state_ref, template = _model_and_state(ds, seeds)

    # ---- reference: the per-batch remote path (one server, ONE
    # worker, prefetch_size=1 — the per-batch path's only
    # DETERMINISTICALLY-ORDERED configuration: with more prefetch
    # slots, concurrent pullers reorder batches within a window, so
    # its loss SEQUENCE is not even self-reproducible. The chunk-
    # staged path removes that nondeterminism by construction.)
    opts = glt.distributed.RemoteDistSamplingWorkerOptions(
        server_rank=0, num_workers=1, prefetch_size=1)
    loader = glt.distributed.RemoteDistNeighborLoader(
        FANOUTS, seeds, batch_size=BS, collect_features=True,
        worker_options=opts, seed=0)
    assert len(loader) == 10
    step, _ = train_lib.make_train_step(model, tx, CLASSES)
    losses_ref = [[], []]
    for e in range(2):
      for b in loader:
        state_ref, loss, _ = step(state_ref, train_lib.batch_to_dict(b))
        losses_ref[e].append(np.asarray(loss))
      assert len(losses_ref[e]) == 10
    loader.shutdown()

    # ---- chunk-staged epochs from an identically-initialized state
    trainer = _make_trainer(model, tx, seeds)
    state_scan, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), template, optimizer=tx)
    assert len(trainer) == 10
    for e in range(2):
      state_scan, losses, accs = trainer.run_epoch(state_scan)
      losses = np.asarray(losses)
      assert losses.shape == (10,) and np.asarray(accs).shape == (10,)
      np.testing.assert_array_equal(
          losses, np.asarray(losses_ref[e]).reshape(-1))
      # chunk-granular ack record: every seed delivered exactly once
      assert sorted(trainer.last_epoch_seed_ids.tolist()) == \
          list(range(N))
    for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                    jax.tree_util.tree_leaves(state_scan.params)):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    trainer.shutdown()
  finally:
    _teardown(pairs)


def test_remote_scan_dispatch_budget_strict():
  """Client dispatch budget: ceil(steps/K) + 2 instrumented program
  dispatches per epoch (begin + chunks + metrics concat) — under
  GLT_STRICT (conftest arms it for this module), so the epoch region
  provably contains nothing but explicit transfers + these programs."""
  ds = make_dataset()
  seeds = np.arange(N)
  pairs = [_start_block_server(ds)]
  try:
    _init_client(pairs)
    model, tx, state, _ = _model_and_state(ds, seeds)
    trainer = _make_trainer(model, tx, seeds)
    steps = len(trainer)
    assert steps == 10
    with glt.utils.count_dispatches() as dc:
      state, losses, _ = trainer.run_epoch(state)
    budget = -(-steps // K) + 2
    total = (dc.counts.get('remote_epoch_begin', 0) +
             dc.counts.get('remote_scan_chunk', 0) +
             dc.counts.get('remote_metrics_concat', 0))
    assert total == budget, dc.counts
    assert dc.counts['remote_scan_chunk'] == -(-steps // K)
    # the only other instrumented launches are the SERVER's sampler
    # programs ('sample') — counted here only because the test server
    # shares this process; in the deployed topology they run on the
    # sampling cluster. Nothing else may ride the client's epoch.
    others = {k: v for k, v in dc.counts.items()
              if not k.startswith('remote_') and k != 'sample'}
    assert not others, f'uninstrumented client dispatches: {dc.counts}'
    # second epoch: no new executables beyond the first epoch's set
    # (one per (k, block shape)) — the retrace sentinel would flag it
    from graphlearn_tpu.metrics import programs
    before = programs.compile_count()
    state, _, _ = trainer.run_epoch(state)
    assert programs.compile_count() == before
    trainer.shutdown()
  finally:
    _teardown(pairs)


@pytest.mark.slow  # tier-1 budget (PR 16): contract sweep overlaps the
# bit-identity-vs-per-batch test, which stays tier-1
def test_remote_scan_vs_collocated_contract():
  """The three-trainer matrix at one scale (40 seeds, global batch 4):
  per-batch remote, chunk-staged remote and collocated DistScanTrainer
  run the same step count over the same seed set. Bit-identity holds
  within the remote pair (asserted above — their streams are the same
  counter replay); the collocated mesh samples a different (equally
  exact) stream, so its leg pins the epoch CONTRACT: steps, coverage,
  finite losses. The wall-clock leg (remote against collocated) is not
  measured on the chip."""
  import jax
  from graphlearn_tpu.typing import GraphPartitionData
  n = 40
  ds = make_dataset(n)
  seeds = np.arange(n)
  pairs = [_start_block_server(ds)]
  try:
    _init_client(pairs)
    model, tx, state, _ = _model_and_state(ds, seeds)
    trainer = _make_trainer(model, tx, seeds)
    state, losses, _ = trainer.run_epoch(state)
    assert np.asarray(losses).shape == (10,)
    assert np.all(np.isfinite(np.asarray(losses)))
    assert sorted(trainer.last_epoch_seed_ids.tolist()) == \
        list(range(n))
    trainer.shutdown()

    # collocated DistScanTrainer at the same scale: 2 shards x bs 2
    # (global batch 4, same 10 steps over the same 40 seeds)
    from jax.sharding import Mesh
    rows = np.concatenate([np.arange(n), np.arange(n)])
    cols = np.concatenate([(np.arange(n) + 1) % n,
                           (np.arange(n) + 2) % n])
    eids = np.arange(2 * n)
    node_pb = (np.arange(n) % 2).astype(np.int32)
    edge_pb = node_pb[rows]
    parts, feats = [], []
    for p in range(2):
      m = edge_pb == p
      parts.append(GraphPartitionData(
          edge_index=np.stack([rows[m], cols[m]]), eids=eids[m]))
      ids = np.nonzero(node_pb == p)[0]
      feats.append((ids.astype(np.int64),
                    ids[:, None].astype(np.float32) *
                    np.ones((1, 4), np.float32)))
    mesh = Mesh(np.array(jax.devices()[:2]), ('g',))
    dg = glt.distributed.DistGraph(2, 0, parts, node_pb, edge_pb)
    df = glt.distributed.DistFeature(2, feats, node_pb, mesh,
                                     split_ratio=0.25)
    dds = glt.distributed.DistDataset(2, 0, dg, df,
                                      node_labels=np.arange(n) % CLASSES)
    dloader = glt.distributed.DistNeighborLoader(
        dds, FANOUTS, seeds, batch_size=2, seed=0, mesh=mesh,
        shuffle=False, drop_last=False)
    assert len(dloader) == 10   # same optimizer-step grid
    dmodel = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
    import optax
    dtx = optax.adam(3e-3)
    dtrainer = glt.loader.DistScanTrainer(dloader, dmodel, dtx, CLASSES,
                                          chunk_size=K)
    first = next(iter(dloader))
    params = dmodel.init(jax.random.PRNGKey(0),
                         np.asarray(first.x)[0],
                         np.asarray(first.edge_index)[0],
                         np.asarray(first.edge_mask)[0])
    import jax.numpy as jnp
    dstate = train_lib.TrainState(params, dtx.init(params), jnp.int32(0))
    dstate, dlosses, _ = dtrainer.run_epoch(dstate)
    assert np.asarray(dlosses).shape == (10,)
    assert np.all(np.isfinite(np.asarray(dlosses)))
  finally:
    _teardown(pairs)


# ------------------------------------------------------ chaos: degrade


def test_block_fetch_fault_degrades_sync_bit_identical(monkeypatch,
                                                       tmp_path):
  """An armed remote.block_fetch fault kills the stager worker's fetch;
  the chunk boundary degrades to a synchronous fetch of the SAME block
  — the epoch completes bit-identically to the healthy run, with the
  degradation visible in remote.prefetch_miss and the fault counter."""
  import jax
  run_log = tmp_path / 'degrade.jsonl'
  monkeypatch.setenv('GLT_RUN_LOG', str(run_log))
  ds = make_dataset()
  seeds = np.arange(N)
  pairs = [_start_block_server(ds)]
  try:
    _init_client(pairs)
    model, tx, state_a, template = _model_and_state(ds, seeds)

    clean = _make_trainer(model, tx, seeds)
    state_a, losses_clean, _ = clean.run_epoch(state_a)
    clean.shutdown()

    state_b, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), template, optimizer=tx)
    armed = _make_trainer(model, tx, seeds)
    faults.arm('remote.block_fetch', 'raise', times=2)
    state_b, losses_armed, _ = armed.run_epoch(state_b)
    np.testing.assert_array_equal(np.asarray(losses_armed),
                                  np.asarray(losses_clean))
    for a, b in zip(jax.tree_util.tree_leaves(state_a.params),
                    jax.tree_util.tree_leaves(state_b.params)):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert trace.counter_get('fault.remote.block_fetch') == 2
    assert trace.counter_get('remote.prefetch_miss') >= 1
    assert armed._stager.degraded
    armed.shutdown()
    from graphlearn_tpu.metrics import flight
    rec = [r for r in flight.read_records(str(run_log))
           if r['emitter'] == 'RemoteScanTrainer'][-1]
    assert rec['completed'] is True and rec['steps'] == 10
  finally:
    _teardown(pairs)


# ---------------------------------------------------- chaos: failover


class _DeadRankClient:
  """Deterministic in-proc stand-in for a dead server endpoint: every
  RPC to a rank in ``dead`` raises ConnectionError (what a TCP reset
  surfaces as); everything else delegates. The real-process SIGKILL
  variant below exercises the true TCP/heartbeat path."""

  def __init__(self, real, dead):
    self._real = real
    self._dead = dead

  def request_server(self, rank, fn, *a, **kw):
    if rank in self._dead:
      raise ConnectionError(f'rank {rank} dead (injected)')
    return self._real.request_server(rank, fn, *a, **kw)

  def async_request_server(self, rank, fn, *a, **kw):
    if rank in self._dead:
      raise ConnectionError(f'rank {rank} dead (injected)')
    return self._real.async_request_server(rank, fn, *a, **kw)


def test_remote_scan_server_death_chunk_failover(monkeypatch, tmp_path):
  """Two servers; rank 1's endpoint dies after the first chunk. Its
  pending blocks are re-replayed by the survivor FROM THE SAME COUNTER
  STREAM: the epoch completes with exact seed coverage, bit-identical
  losses to the undisturbed 2-server run, and an orphan-free span tree
  whose loader.failover span parents under the epoch root."""
  run_log = tmp_path / 'failover.jsonl'
  monkeypatch.setenv('GLT_RUN_LOG', str(run_log))
  ds = make_dataset(40)
  seeds = np.arange(40)
  pairs = [_start_block_server(ds) for _ in range(2)]
  # block_ahead=1: the kill must land while the victim still OWNS
  # pending blocks (a deeper ring could prefetch its whole share
  # before the death, making the scenario vacuous)
  opts = lambda: glt.distributed.RemoteDistSamplingWorkerOptions(  # noqa: E731
      server_rank=[0, 1], heartbeat_interval=0.2, heartbeat_miss=2,
      block_ahead=1)
  try:
    _init_client(pairs)
    model, tx, state_a, template = _model_and_state(ds, seeds)

    clean = _make_trainer(model, tx, seeds, worker_options=opts())
    assert len(clean) == 10     # 2 streams x 20 seeds / bs 4
    state_a, losses_clean, _ = clean.run_epoch(state_a)
    assert sorted(clean.last_epoch_seed_ids.tolist()) == list(range(40))
    clean.shutdown()

    import jax
    from graphlearn_tpu.metrics import spans
    state_b, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), template, optimizer=tx)
    victim = _make_trainer(model, tx, seeds, worker_options=opts())
    spans.reset()
    from graphlearn_tpu.distributed import dist_client
    dead = set()
    victim._dist_client = _DeadRankClient(dist_client, dead)

    def killer(c, start, k):
      # kill rank 1's endpoint right after the FIRST chunk trains —
      # mid-epoch, while its stream still owns pending blocks
      if c == 0:
        dead.add(1)

    victim.ack_hook = killer
    state_b, losses_b, _ = victim.run_epoch(state_b)
    np.testing.assert_array_equal(np.asarray(losses_b),
                                  np.asarray(losses_clean))
    assert sorted(victim.last_epoch_seed_ids.tolist()) == \
        list(range(40))
    assert 1 in victim._dead_ranks
    assert trace.counter_get('remote.failover_blocks') >= 1
    assert trace.counter_get('resilience.failover') >= 1

    # span acceptance: one joinable, orphan-free tree (client ring +
    # the in-process servers' handle/stage spans share the ring); the
    # failover span hangs off the completed epoch root
    collected = list(spans.export(trace=spans.run_id()))
    tree = spans.build_tree(collected)
    assert tree['orphans'] == []
    by_name = {}
    for r in collected:
      by_name.setdefault(r['name'], []).append(r)
    [root] = [r for r in by_name['epoch.run']
              if r['attrs'].get('completed')]
    fos = by_name['loader.failover']
    assert fos and all(f['parent'] == root['span'] for f in fos)
    assert any(f['attrs'].get('blocks', 0) >= 1 and
               'cause' in f['attrs'] for f in fos)
    assert by_name.get('remote.block_fetch')

    # epoch 2 against the degraded cluster: the dead rank's whole
    # share re-points to the survivor at schedule build
    state_b, losses_e2, _ = victim.run_epoch(state_b)
    assert np.asarray(losses_e2).shape == (10,)
    assert sorted(victim.last_epoch_seed_ids.tolist()) == \
        list(range(40))
    victim.shutdown()

    from graphlearn_tpu.metrics import flight
    recs = [r for r in flight.read_records(str(run_log))
            if r['emitter'] == 'RemoteScanTrainer']
    degraded = [r for r in recs if r.get('dead_ranks')]
    assert degraded and degraded[0]['completed'] is True
    assert '1' in degraded[0]['dead_ranks']
  finally:
    _teardown(pairs)


def test_failover_disabled_raises():
  """failover=False is an explicit operator choice: a dead rank with
  pending blocks fails LOUDLY instead of silently re-pointing, and the
  refusal leaves no sticky dead mark."""
  ds = make_dataset()
  seeds = np.arange(N)
  pairs = [_start_block_server(ds) for _ in range(2)]
  try:
    _init_client(pairs)
    model, tx, state, _ = _model_and_state(ds, seeds)
    opts = glt.distributed.RemoteDistSamplingWorkerOptions(
        server_rank=[0, 1], heartbeat_interval=0.2, heartbeat_miss=2,
        failover=False)
    trainer = _make_trainer(model, tx, seeds, worker_options=opts)
    trainer._schedule = trainer._block_schedule(len(trainer), 0)
    with pytest.raises(RuntimeError, match='failover is disabled'):
      trainer._handle_dead_rank(1, 'test', 0)
    assert 1 not in trainer._dead_ranks   # no sticky mark on refusal
    trainer.shutdown()
  finally:
    _teardown(pairs)


def test_remote_scan_shuffle_failover_exact_coverage():
  """ROADMAP 1b, lifted in round 15: shuffle=True failover is EXACT —
  the server epoch permutation is a pure function of (stream seed,
  epoch) (block_producer._epoch_order), so a survivor's replay
  producer re-draws the dead rank's order identically. A mid-epoch
  server kill completes the shuffled epoch with exact seed coverage
  AND losses bit-identical to the undisturbed 2-server shuffled run."""
  import jax
  ds = make_dataset(40)
  seeds = np.arange(40)
  pairs = [_start_block_server(ds) for _ in range(2)]
  opts = lambda: glt.distributed.RemoteDistSamplingWorkerOptions(  # noqa: E731
      server_rank=[0, 1], heartbeat_interval=0.2, heartbeat_miss=2,
      block_ahead=1)   # the victim must still OWN pending blocks
  try:
    _init_client(pairs)
    model, tx, state_a, template = _model_and_state(ds, seeds)

    clean = _make_trainer(model, tx, seeds, shuffle=True,
                          worker_options=opts())
    state_a, losses_clean, _ = clean.run_epoch(state_a)
    assert sorted(clean.last_epoch_seed_ids.tolist()) == list(range(40))
    clean.shutdown()

    state_b, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), template, optimizer=tx)
    victim = _make_trainer(model, tx, seeds, shuffle=True,
                           worker_options=opts())
    from graphlearn_tpu.distributed import dist_client
    dead = set()
    victim._dist_client = _DeadRankClient(dist_client, dead)

    def killer(c, start, k):
      if c == 0:       # kill rank 1 right after the first chunk trains
        dead.add(1)

    victim.ack_hook = killer
    state_b, losses_b, _ = victim.run_epoch(state_b)
    # exact seed coverage of the SHUFFLED epoch after the kill — the
    # acceptance this satellite pins
    assert sorted(victim.last_epoch_seed_ids.tolist()) == \
        list(range(40))
    assert 1 in victim._dead_ranks
    # stronger than coverage: the survivor replayed the identical
    # permuted blocks, so the losses match the undisturbed run bitwise
    np.testing.assert_array_equal(np.asarray(losses_b),
                                  np.asarray(losses_clean))
    assert trace.counter_get('remote.failover_blocks') >= 1
    # epoch 2 on the degraded cluster re-points the whole share at
    # schedule build and still covers every seed of ITS permutation
    state_b, losses_e2, _ = victim.run_epoch(state_b)
    assert sorted(victim.last_epoch_seed_ids.tolist()) == \
        list(range(40))
    victim.shutdown()
  finally:
    _teardown(pairs)


# ------------------------------------------------------ crash + resume


def test_remote_scan_crash_resume_block_boundary(tmp_path):
  """ChunkCheckpointer rides the ack_hook seam unchanged: a crash at
  chunk 2 resumes in a FRESH trainer from the block boundary —
  whole-epoch losses and final params bit-identical to the
  uninterrupted run (the server streams are counter-addressed, so the
  resumed epoch re-fetches its remaining blocks exactly)."""
  import jax

  from graphlearn_tpu.recovery import ChunkCheckpointer
  ds = make_dataset()
  seeds = np.arange(N)
  pairs = [_start_block_server(ds)]
  try:
    _init_client(pairs)
    model, tx, state_a, template = _model_and_state(ds, seeds)

    ref = _make_trainer(model, tx, seeds)
    state_a, losses_ref, accs_ref = ref.run_epoch(state_a)
    ref.shutdown()

    ckdir = str(tmp_path / 'ck')
    victim = _make_trainer(model, tx, seeds)
    ck = ChunkCheckpointer(ckdir, every=1).attach(victim)

    def crash(c, start, k):
      if c == 2:
        raise RuntimeError('injected mid-epoch crash')

    prev = victim.stage_hook
    victim.stage_hook = crash
    del prev
    state_b, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), template, optimizer=tx)
    with pytest.raises(RuntimeError, match='injected'):
      victim.run_epoch(state_b)
    ck.close()
    victim.shutdown()

    fresh = _make_trainer(model, tx, seeds)
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
    assert fresh._epochs == 1    # counters continued past the epoch
    fresh.shutdown()
  finally:
    _teardown(pairs)


# ----------------------------------------------------- wire dtype


def test_remote_scan_bf16_wire():
  """block_wire_dtype='bf16' halves the feature payload on the wire
  (f32 upcast happens inside the chunk program after upload); the
  epoch trains to finite losses close to the f32 run — a precision
  delta, never a correctness one."""
  import ml_dtypes
  ds = make_dataset()
  seeds = np.arange(N)
  pairs = [_start_block_server(ds)]
  try:
    _init_client(pairs)
    model, tx, state, template = _model_and_state(ds, seeds)

    f32 = _make_trainer(model, tx, seeds)
    state_f32, losses_f32, _ = f32.run_epoch(state)
    f32.shutdown()

    opts = glt.distributed.RemoteDistSamplingWorkerOptions(
        server_rank=0, block_wire_dtype='bf16')
    import jax
    state_b, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), template, optimizer=tx)
    bf = _make_trainer(model, tx, seeds, worker_options=opts)
    state_b, losses_bf, _ = bf.run_epoch(state_b)
    losses_bf = np.asarray(losses_bf)
    assert np.all(np.isfinite(losses_bf))
    np.testing.assert_allclose(losses_bf, np.asarray(losses_f32),
                               rtol=0.1, atol=0.1)
    bf.shutdown()

    # the frame itself ships half-width features
    from graphlearn_tpu.distributed import block_mb_per_chunk
    from graphlearn_tpu.distributed.block_producer import \
        BlockSampleProducer
    from graphlearn_tpu.sampler import SamplingConfig, SamplingType
    cfg = SamplingConfig(SamplingType.NODE, FANOUTS, BS, False, False,
                         False, True, False, False, 'out', 0)
    bp32 = BlockSampleProducer(ds, seeds, cfg)
    bp16 = BlockSampleProducer(ds, seeds, cfg, wire_dtype='bf16')
    fr32, fr16 = bp32.build_frame(0, 0, 4), bp16.build_frame(0, 0, 4)
    assert fr16['x'].dtype == ml_dtypes.bfloat16
    assert fr16['x'].nbytes * 2 == fr32['x'].nbytes
    # the analytic accounting tracks the actual x payload
    assert block_mb_per_chunk(4, fr32['x'].shape[1], 24, 4, 'bf16') < \
        block_mb_per_chunk(4, fr32['x'].shape[1], 24, 4, None)
  finally:
    _teardown(pairs)


# --------------------------------------------------------- scope errors


def test_scope_validation_messages_name_chunk_staged_path():
  """DistFusedEpochTrainer's remote rejection now points at the
  chunk-staged path (whose failover is exact even under shuffle=True
  — round 15) instead of flatly rejecting; RemoteScanTrainer accepts
  typed seeds (the hetero block streams) and rejects only what it
  cannot train (collect_features=False)."""
  with pytest.raises(ValueError) as ei:
    glt.loader.DistFusedEpochTrainer(object(), None, None, 3)
  msg = str(ei.value)
  assert 'RemoteScanTrainer' in msg
  assert 'shuffle=True' in msg
  assert 'remote_scan' in msg

  with pytest.raises(ValueError, match='collect_features'):
    glt.distributed.RemoteScanTrainer(
        FANOUTS, np.arange(4), None, None, 3, collect_features=False)


# -------------------------------------------------- real-process SIGKILL


def _block_server_main(rank, q, ready):
  import jax
  try:
    jax.config.update('jax_platforms', 'cpu')
  except RuntimeError:
    pass
  import graphlearn_tpu as glt_mod
  import numpy as np_mod
  n = 40
  rows = np_mod.concatenate([np_mod.arange(n), np_mod.arange(n)])
  cols = np_mod.concatenate([(np_mod.arange(n) + 1) % n,
                             (np_mod.arange(n) + 2) % n])
  ds = glt_mod.data.Dataset()
  ds.init_graph(np_mod.stack([rows, cols]), graph_mode='CPU',
                num_nodes=n)
  feat = np_mod.arange(n, dtype=np_mod.float32)[:, None] * \
      np_mod.ones((1, 4), np_mod.float32)
  ds.init_node_features(feat)
  ds.init_node_labels(np_mod.arange(n) % 3)
  host, port = glt_mod.distributed.init_server(
      num_servers=2, num_clients=1, server_rank=rank, dataset=ds)
  q.put((rank, host, port))
  ready.wait(timeout=180)
  glt_mod.distributed.wait_and_shutdown_server(timeout=300)


@pytest.mark.slow   # tier-1 budget: the in-proc endpoint-death variant
def test_remote_scan_sigkill_server_failover():   # stays tier-1
  """A REAL SIGKILL mid-epoch: the heartbeat (or the fetch's TCP
  reset) declares the victim dead, survivors re-replay its pending
  blocks, and the epoch completes with exact seed coverage."""
  ctx = mp.get_context('spawn')
  q = ctx.Queue()
  ready = ctx.Event()
  servers = [ctx.Process(target=_block_server_main, args=(r, q, ready))
             for r in range(2)]
  try:
    for s in servers:
      s.start()
    addrs = {}
    for _ in range(2):
      r, host, port = q.get(timeout=180)
      addrs[r] = (host, port)
    ready.set()
    glt.distributed.init_client(
        num_servers=2, num_clients=1, client_rank=0,
        server_addrs=[addrs[0], addrs[1]])
    ds = make_dataset(40)
    seeds = np.arange(40)
    model, tx, state, _ = _model_and_state(ds, seeds)
    opts = glt.distributed.RemoteDistSamplingWorkerOptions(
        server_rank=[0, 1], heartbeat_interval=0.3, heartbeat_miss=2,
        block_ahead=1)
    trainer = _make_trainer(model, tx, seeds, worker_options=opts)

    def killer(c, start, k):
      if c == 0 and servers[1].is_alive():
        os.kill(servers[1].pid, signal.SIGKILL)

    trainer.ack_hook = killer
    t0 = time.monotonic()
    state, losses, _ = trainer.run_epoch(state)
    assert np.asarray(losses).shape == (10,)
    assert sorted(trainer.last_epoch_seed_ids.tolist()) == \
        list(range(40))
    assert 1 in trainer._dead_ranks
    assert trace.counter_get('remote.failover_blocks') >= 1
    assert time.monotonic() - t0 < 120
    trainer.shutdown()
    glt.distributed.shutdown_client()
  finally:
    for s in servers:
      if s.is_alive():
        s.terminate()
      s.join(timeout=30)
