"""Unified metrics layer (ISSUE 6): typed registry, trace shims,
histogram quantiles, cross-process scrape, epoch flight recorder,
graftlint metric-registry rule, and the bench trajectory gate.

The acceptance pins: (1) a flight record's dispatch/feature fields
bit-match the live counters with ZERO extra dispatches (the scanned
epoch's ceil(steps/K)+2 budget holds with recording on, under
GLT_STRICT); (2) a remote-server + mp-producer run scrapes a merged,
role-labelled snapshot at the client, retry-safe under the
fault-injection registry."""
import json
import os
import threading
import time

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu import metrics
from graphlearn_tpu.metrics import flight
from graphlearn_tpu.metrics.registry import (HIST_BOUNDS, MetricRegistry,
                                             merge_snapshots,
                                             quantile_from_state)
from graphlearn_tpu.utils import faults, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
  faults.disarm()
  metrics.reset()
  yield
  faults.disarm()
  metrics.reset()


# ------------------------------------------------------------- registry


def test_counter_gauge_histogram_basics():
  reg = MetricRegistry()
  reg.inc('a.hits')
  reg.inc('a.hits', 4)
  assert reg.counter('a.hits').value == 5
  reg.set_gauge('a.depth', 3.5)
  assert reg.gauge('a.depth').value == 3.5
  reg.observe('a.lat_ms', 2.0)
  reg.observe('a.lat_ms', 8.0)
  h = reg.histogram('a.lat_ms')
  assert h.count == 2 and h.sum == 10.0
  snap = reg.snapshot()
  assert snap['counters'] == {'a.hits': 5}
  assert snap['gauges'] == {'a.depth': 3.5}
  assert snap['histograms']['a.lat_ms']['count'] == 2
  assert snap['histograms']['a.lat_ms']['min'] == 2.0
  # snapshots are JSON-able end to end (the cross-process contract)
  json.dumps(snap)


def test_one_name_one_type():
  reg = MetricRegistry()
  reg.inc('x.n')
  with pytest.raises(ValueError, match='one name, one type'):
    reg.observe('x.n', 1.0)


def test_reset_prefix_counters_only():
  reg = MetricRegistry()
  reg.inc('a.x')
  reg.inc('b.x')
  reg.observe('a.lat_ms', 1.0)
  reg.reset_counters('a.')
  assert reg.counters() == {'b.x': 1}
  assert reg.histogram('a.lat_ms').count == 1   # untouched
  reg.reset()
  assert reg.snapshot() == {'counters': {}, 'gauges': {},
                            'histograms': {}}


def test_trace_shims_feed_the_registry():
  """counter_inc/counters/counter_get/reset_counters are views of the
  default registry — the ~10 pre-existing call sites and the new
  metrics surface share one store."""
  trace.counter_inc('resilience.retry', 2)
  assert metrics.snapshot()['counters'] == {'resilience.retry': 2}
  metrics.inc('resilience.retry')
  assert trace.counter_get('resilience.retry') == 3
  assert trace.counters('resilience') == {'resilience.retry': 3}
  metrics.observe('rpc.client.request_ms', 1.0)
  trace.reset_counters()
  assert trace.counters() == {}
  # the old dict semantics: reset_counters leaves non-counters alone
  assert metrics.histogram('rpc.client.request_ms').count == 1


def test_registry_thread_stress():
  """Concurrent inc/observe/snapshot from many threads (the heartbeat +
  puller + RPC-handler shape) lose nothing: final counts are exact."""
  reg = MetricRegistry()
  n_threads, n_iter = 6, 3000
  errors = []

  def writer():
    try:
      for i in range(n_iter):
        reg.inc('s.events')
        if i % 3 == 0:
          reg.observe('s.lat_ms', 0.5 + (i % 100))
        if i % 7 == 0:
          reg.set_gauge('s.depth', i)
    except Exception as e:  # noqa: BLE001
      errors.append(e)

  def reader():
    try:
      for _ in range(200):
        snap = reg.snapshot()
        assert snap['counters'].get('s.events', 0) >= 0
        reg.counters('s.')
    except Exception as e:  # noqa: BLE001
      errors.append(e)

  threads = [threading.Thread(target=writer) for _ in range(n_threads)]
  threads += [threading.Thread(target=reader) for _ in range(2)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  assert not errors
  assert reg.counter('s.events').value == n_threads * n_iter
  expect_obs = n_threads * len(range(0, n_iter, 3))
  assert reg.histogram('s.lat_ms').count == expect_obs


@pytest.mark.parametrize('dist', ['lognormal', 'uniform', 'exponential'])
def test_histogram_quantiles_vs_numpy(dist):
  """p50/p95/p99 estimates land within one log-bucket ratio (~1.78x)
  of numpy's exact sample percentiles on known distributions."""
  rng = np.random.default_rng(0)
  if dist == 'lognormal':
    xs = rng.lognormal(mean=1.0, sigma=1.5, size=20000)
  elif dist == 'uniform':
    xs = rng.uniform(0.3, 250.0, size=20000)
  else:
    xs = rng.exponential(scale=30.0, size=20000)
  reg = MetricRegistry()
  h = reg.histogram('q.lat_ms')
  for x in xs:
    h.observe(float(x))
  bucket_ratio = HIST_BOUNDS[1] / HIST_BOUNDS[0]   # 10^(1/4)
  for q in (0.5, 0.95, 0.99):
    exact = float(np.percentile(xs, 100 * q))
    est = h.quantile(q)
    assert est is not None
    ratio = est / exact
    assert 1 / (bucket_ratio * 1.01) <= ratio <= bucket_ratio * 1.01, \
        f'{dist} p{int(q * 100)}: est {est:.3f} vs exact {exact:.3f}'
  assert h.quantile(0.0) == pytest.approx(float(xs.min()))
  assert h.quantile(1.0) == pytest.approx(float(xs.max()))


def test_merge_snapshots_and_cluster_quantiles():
  a, b = MetricRegistry(), MetricRegistry()
  a.inc('n.x', 2)
  b.inc('n.x', 3)
  b.inc('n.y')
  a.set_gauge('n.g', 1.0)
  b.set_gauge('n.g', 2.0)
  for v in (1.0, 10.0):
    a.observe('n.lat_ms', v)
  for v in (100.0, 1000.0):
    b.observe('n.lat_ms', v)
  m = merge_snapshots([a.snapshot(), b.snapshot()])
  assert m['counters'] == {'n.x': 5, 'n.y': 1}
  assert m['gauges'] == {'n.g': 2.0}          # last writer
  h = m['histograms']['n.lat_ms']
  assert h['count'] == 4 and h['sum'] == 1111.0
  assert h['min'] == 1.0 and h['max'] == 1000.0
  assert quantile_from_state(h, 1.0) == 1000.0
  # schema mismatch refuses to merge
  bad = a.snapshot()
  bad['histograms']['n.lat_ms']['buckets'] = 'log10:2/decade:0..3'
  with pytest.raises(ValueError, match='bucket schema'):
    merge_snapshots([b.snapshot(), bad])


# ------------------------------------- dispatch-counter nesting satellite


def test_count_dispatches_propagate():
  with trace.count_dispatches() as outer:
    trace.record_dispatch('a')
    with trace.count_dispatches(propagate=True) as inner:
      trace.record_dispatch('a')
      trace.record_dispatch('b')
    assert inner.counts == {'a': 1, 'b': 1}
    with trace.count_dispatches() as isolated:   # default: no propagate
      trace.record_dispatch('c')
    assert isolated.counts == {'c': 1}
  assert outer.counts == {'a': 2, 'b': 1}
  # top-level propagate has no outer counter: a no-op, not an error
  with trace.count_dispatches(propagate=True) as top:
    trace.record_dispatch('d')
  assert top.counts == {'d': 1}


# ------------------------------------------- the glt. profiler convention


def test_metrics_imports_and_records_spans_without_jax():
  """mp sampling workers and lint fixtures load metrics/ in a process
  where jax is not importable: spans must import, record, and skip the
  profiler annotation — in a subprocess, so this process's jax (and the
  package __init__ that imports it) is out of the picture."""
  import subprocess
  import sys
  import textwrap
  code = textwrap.dedent("""
      import sys, types
      sys.modules['jax'] = None          # `import jax` raises ImportError
      pkg = types.ModuleType('graphlearn_tpu')
      pkg.__path__ = ['graphlearn_tpu']  # the package minus its __init__
      sys.modules['graphlearn_tpu'] = pkg
      from graphlearn_tpu.metrics import spans
      with spans.span('epoch.chunk', k=4) as tok:
        assert tok.annotation is None
      rec = spans.export()[-1]
      assert rec['name'] == 'epoch.chunk' and rec['attrs'] == {'k': 4}
      assert [m for m in sys.modules if m.startswith('jax')] == ['jax']
      print('recorded', rec['dur_ms'] >= 0)
  """)
  out = subprocess.run([sys.executable, '-c', code], cwd=REPO, timeout=60,
                       capture_output=True, text=True)
  assert out.returncode == 0, out.stderr
  assert out.stdout.strip() == 'recorded True'


@pytest.mark.parametrize('name', sorted(
    metrics.registry_names.REGISTERED_SCOPES |
    {'glt.epoch.seeds', 'glt.epoch.concat', 'glt.epoch.hook',
     'glt.loader.batch'}))
def test_every_profiler_name_is_documented(name):
  """One table of glt.* names (metrics/registry_names.py), listed in
  docs/observability.md — the same closed-namespace contract as metric
  and span names; a host name is 'glt.' + a registered span."""
  with open(os.path.join(REPO, 'docs', 'observability.md')) as f:
    doc = f.read()
  assert f'`{name}`' in doc
  if name.startswith('glt.epoch.') or name.startswith('glt.loader.'):
    assert name[len('glt.'):] in metrics.REGISTERED_SPANS
  else:
    assert name.split('/')[0] in (
        metrics.registry_names.SCOPE_SAMPLE,
        metrics.registry_names.SCOPE_COLLATE,
        metrics.registry_names.SCOPE_TRAIN,
        metrics.registry_names.SCOPE_PLAN)


# --------------------------------------------------- epoch flight records


def _scan_fixture(num_seeds=24, batch=8, chunk=2):
  from graphlearn_tpu.models import GraphSAGE, train as train_lib
  n = 96
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(n), 4)
  cols = (rows + rng.integers(1, n, rows.shape[0])) % n
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=n)
  ds.init_node_features(rng.standard_normal((n, 6)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 3, n))
  pool = rng.permutation(n)[:num_seeds].astype(np.int64)
  loader = glt.loader.NeighborLoader(ds, [3, 2], pool, batch_size=batch,
                                     shuffle=False, seed=0)
  model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  import jax
  first = train_lib.batch_to_dict(next(iter(
      glt.loader.NeighborLoader(ds, [3, 2], pool, batch_size=batch,
                                seed=0))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  trainer = glt.loader.ScanTrainer(loader, model, tx, 3,
                                   chunk_size=chunk)
  return trainer, state


def test_flight_record_scan_trainer_bitmatch(monkeypatch, tmp_path):
  """Acceptance: one ScanTrainer epoch under count_dispatches +
  GLT_RUN_LOG yields a record whose dispatch fields BIT-MATCH the live
  counter — and the epoch's dispatch budget stays at ceil(steps/K)+2,
  i.e. recording adds ZERO program dispatches, under GLT_STRICT's
  transfer guard (zero device->host fetches in the epoch region)."""
  log = tmp_path / 'run.jsonl'
  trainer, state = _scan_fixture()          # 24 seeds / bs 8 = 3 steps
  # recording armed only now: the fixture's template-batch iteration
  # would otherwise (correctly) write its own per-step loader record
  monkeypatch.setenv('GLT_RUN_LOG', str(log))
  monkeypatch.setenv('GLT_STRICT', '1')
  with trace.count_dispatches() as dc:
    state, losses, _ = trainer.run_epoch(state)
  steps = int(np.asarray(losses).shape[0])
  assert steps == 3
  assert dc.total == -(-steps // trainer.chunk_size) + 2   # ceil+2
  recs = flight.read_records(str(log))
  assert len(recs) == 1
  rec = recs[0]
  assert rec['emitter'] == 'ScanTrainer'
  assert rec['epoch'] == 0 and rec['steps'] == steps
  assert rec['completed'] is True
  assert rec['dispatch'] == dc.counts          # bit-match
  assert rec['dispatch_total'] == dc.total
  assert rec['wall_s'] > 0
  assert rec['config']['chunk_size'] == 2
  fp = rec['config_fingerprint']

  # epoch 2: same fingerprint (same config), epoch counter advances,
  # and deltas stay per-epoch even though the outer counter accumulates
  with trace.count_dispatches() as dc2:
    state, losses2, _ = trainer.run_epoch(state)
  rec2 = flight.read_records(str(log))[1]
  assert rec2['epoch'] == 1
  assert rec2['config_fingerprint'] == fp
  assert rec2['dispatch'] == dc2.counts


def test_flight_record_failed_epoch_completed_false(monkeypatch,
                                                    tmp_path):
  """A mid-scan failure still writes the epoch's record — completed
  False, under the UN-advanced epoch number the re-run will redraw —
  so the postmortem log keeps exactly the epoch it exists for."""
  log = tmp_path / 'run.jsonl'
  trainer, state = _scan_fixture()
  monkeypatch.setenv('GLT_RUN_LOG', str(log))

  def boom(*a, **k):
    raise RuntimeError('chunk dispatch failed')

  monkeypatch.setattr(trainer, '_chunk_fn', boom)
  with pytest.raises(RuntimeError, match='chunk dispatch'):
    trainer.run_epoch(state)
  rec = flight.read_records(str(log))[-1]
  assert rec['completed'] is False
  assert rec['emitter'] == 'ScanTrainer' and rec['epoch'] == 0
  # steps = what the scan actually dispatched (first chunk failed),
  # matching the per-step emitters' delivered-batch semantics
  assert rec['steps'] == 0
  # the re-run records the SAME epoch number (permutation replays)
  monkeypatch.undo()
  monkeypatch.setenv('GLT_RUN_LOG', str(log))
  state, losses, _ = trainer.run_epoch(state)
  rec2 = flight.read_records(str(log))[-1]
  assert rec2['completed'] is True and rec2['epoch'] == 0


def test_flight_recording_off_is_free(tmp_path, monkeypatch):
  monkeypatch.delenv('GLT_RUN_LOG', raising=False)
  trainer, state = _scan_fixture()
  trainer.run_epoch(state)
  assert flight.epoch_begin() is None
  assert flight.epoch_end(None, 'x', 0, 0) is None
  assert list(tmp_path.iterdir()) == []


def _dist_loader(num_parts=2, batch_size=4, split_ratio=0.0):
  from graphlearn_tpu.typing import GraphPartitionData
  N = 40
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
                  ids[:, None].astype(np.float32) * np.ones(
                      (1, 4), np.float32)))
  import jax
  from jax.sharding import Mesh
  mesh = Mesh(np.array(jax.devices()[:num_parts]), ('g',))
  dg = glt.distributed.DistGraph(num_parts, 0, parts, node_pb, edge_pb)
  df = glt.distributed.DistFeature(num_parts, feats, node_pb, mesh,
                                   split_ratio=split_ratio)
  ds = glt.distributed.DistDataset(num_parts, 0, dg, df,
                                   node_labels=np.arange(N) % 4)
  return glt.distributed.DistNeighborLoader(
      ds, [2, 2], np.arange(N), batch_size=batch_size, seed=0,
      mesh=mesh)


def test_flight_record_dist_loader_feature_bitmatch(monkeypatch,
                                                    tmp_path):
  """The per-step distributed loop's record: feature fields equal the
  live dist_feature.*/dist_label.* counters the epoch's own
  publish_stats fetch produced — the recorder adds no fetch of its
  own."""
  log = tmp_path / 'dist.jsonl'
  monkeypatch.setenv('GLT_RUN_LOG', str(log))
  loader = _dist_loader()
  steps = sum(1 for _ in loader)
  assert steps == len(loader) > 0
  rec = flight.read_records(str(log))[-1]
  assert rec['emitter'] == 'DistNeighborLoader'
  assert rec['steps'] == steps and rec['completed'] is True
  live = {**trace.counters('dist_feature'),
          **trace.counters('dist_label')}
  assert live and rec['feature'] == live       # bit-match
  assert rec['dispatch'] is None               # no region was active


@pytest.mark.slow  # tier-1 wall budget (PR 8): the LOCAL ScanTrainer
def test_flight_record_dist_scan_trainer(monkeypatch, tmp_path):
  # flight bit-match stays tier-1, and the dist feature-stats parity is
  # carried by test_dist_scan_epoch's equivalence protocol
  """Acceptance on the SCANNED distributed epoch: the flight record's
  dispatch fields bit-match the live counter at the ceil(steps/K)+2
  budget (recording adds zero dispatches), its feature fields bit-match
  the scan-carry stats published once at epoch end, and the chunk
  programs run fetch-free under GLT_STRICT."""
  import gc

  import jax
  import jax.numpy as jnp
  import optax
  from graphlearn_tpu.models import GraphSAGE, train as train_lib
  loader = _dist_loader(batch_size=2, split_ratio=0.25)
  model = GraphSAGE(hidden_dim=8, out_dim=4, num_layers=2)
  tx = optax.adam(1e-2)
  first = next(iter(_dist_loader(batch_size=2, split_ratio=0.25)))
  params = model.init(jax.random.PRNGKey(0), np.asarray(first.x)[0],
                      np.asarray(first.edge_index)[0],
                      np.asarray(first.edge_mask)[0])
  state = train_lib.TrainState(params, tx.init(params), jnp.int32(0))
  trainer = glt.loader.DistScanTrainer(loader, model, tx, 4,
                                       chunk_size=4)
  gc.collect()                      # drain the template loader's publish
  trace.reset_counters()
  log = tmp_path / 'dist_scan.jsonl'
  monkeypatch.setenv('GLT_RUN_LOG', str(log))
  monkeypatch.setenv('GLT_STRICT', '1')
  with trace.count_dispatches() as dc:
    state, losses, _ = trainer.run_epoch(state)
  steps = int(np.asarray(losses).shape[0])
  assert steps == len(loader) == 10
  assert dc.total == -(-steps // 4) + 2
  rec = flight.read_records(str(log))[-1]
  assert rec['emitter'] == 'DistScanTrainer'
  assert rec['steps'] == steps
  assert rec['dispatch'] == dc.counts
  live = {**trace.counters('dist_feature'),
          **trace.counters('dist_label')}
  assert live.get('dist_feature.lookups', 0) > 0
  assert rec['feature'] == live
  assert rec['config']['mesh'] == {'g': 2}


def test_flight_read_records_skips_garbage(tmp_path):
  p = tmp_path / 'log.jsonl'
  p.write_text('{"schema": 1, "kind": "epoch"}\nnot json\n\n'
               '{"schema": 1, "epoch": 2}\n')
  recs = flight.read_records(str(p))
  assert [r.get('epoch') for r in recs] == [None, 2]
  assert flight.read_records(str(tmp_path / 'missing.jsonl')) == []


# --------------------------------------------- cross-process scrape e2e


def _start_metrics_server(dataset):
  from graphlearn_tpu.distributed.dist_server import DistServer
  from graphlearn_tpu.distributed.rpc import RpcServer
  s = DistServer(dataset)
  rpc = RpcServer(handlers={
      'create_sampling_producer': s.create_sampling_producer,
      'producer_num_expected': s.producer_num_expected,
      'start_new_epoch_sampling': s.start_new_epoch_sampling,
      'fetch_one_sampled_message': s.fetch_one_sampled_message,
      'destroy_sampling_producer': s.destroy_sampling_producer,
      'get_dataset_meta': s.get_dataset_meta,
      'heartbeat': s.heartbeat,
      'get_metrics': s.get_metrics,
      'exit': s.exit,
  })
  return s, rpc


def _chaos_dataset(n=40):
  rows = np.concatenate([np.arange(n), np.arange(n)])
  cols = np.concatenate([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n])
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=n)
  feat = np.arange(n, dtype=np.float32)[:, None] * np.ones((1, 4),
                                                           np.float32)
  ds.init_node_features(feat)
  ds.init_node_labels(np.arange(n) % 3)
  return ds


@pytest.mark.timeout(240)
def test_scrape_all_remote_server_mp_producer():
  """Acceptance: one remote sampling server whose producer runs one mp
  worker — after an epoch the CLIENT scrapes a merged, role-labelled
  snapshot ('client/0', 'server/0', 'server/0/producer/<pid>'), and
  the scrape RPC is retry-safe (idempotent) under an armed
  rpc.client.request fault."""
  from graphlearn_tpu.distributed import dist_client
  N = 40
  ds = _chaos_dataset(N)
  s, rpc = _start_metrics_server(ds)
  try:
    dist_client.init_client(num_servers=1, num_clients=1, client_rank=0,
                            server_addrs=[(rpc.host, rpc.port)])
    opts = glt.distributed.RemoteDistSamplingWorkerOptions(
        server_rank=[0], num_workers=1, prefetch_size=2)
    loader = glt.distributed.RemoteDistNeighborLoader(
        [2, 2], np.arange(N), batch_size=4, collect_features=True,
        worker_options=opts, seed=0)
    expected = len(loader)
    count = sum(1 for _ in loader)
    assert count == expected

    # the worker publishes its snapshot at epoch end over the metrics
    # queue — poll briefly for the cross-process handoff
    producer_roles = {}
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
      scrapes = metrics.scrape_all()
      producer_roles = {r: s_ for r, s_ in scrapes.items()
                        if '/producer/' in r}
      if producer_roles:
        break
      time.sleep(0.25)

    assert 'client/0' in scrapes
    assert 'server/0' in scrapes and 'error' not in scrapes['server/0']
    assert producer_roles, f'no producer role in {sorted(scrapes)}'
    prod = next(iter(producer_roles.values()))
    assert prod['counters']['producer.batches'] == expected
    assert prod['histograms']['producer.sample_ms']['count'] == expected
    # the server's own registry saw every delivered fetch
    assert scrapes['server/0']['histograms']['server.fetch_ms'][
        'count'] >= expected
    # client-side: RPC latency histogram populated by the stream
    assert scrapes['client/0']['histograms']['rpc.client.request_ms'][
        'count'] > 0

    # merged cluster view: counters add across roles
    merged = metrics.merge_scrape(scrapes)
    assert merged['counters']['producer.batches'] == expected
    assert merged['histograms']['server.fetch_ms']['count'] >= expected

    # retry safety: one injected request failure, scrape still lands
    # (get_metrics is idempotent, so the retry path is allowed)
    faults.arm('rpc.client.request', 'raise', exc=ConnectionError,
               times=1)
    scrapes2 = metrics.scrape_all()
    assert 'error' not in scrapes2['server/0']
    assert trace.counter_get('fault.rpc.client.request') >= 1
    assert trace.counter_get('resilience.retry') >= 1
    loader.shutdown()
  finally:
    faults.disarm()
    dist_client._client.close()
    dist_client._client = None
    s.exit()
    rpc.shutdown()


def test_scrape_local_sources_degrade():
  metrics.register_source('producer/7', lambda: {
      'counters': {'producer.batches': 3}, 'gauges': {},
      'histograms': {}})
  metrics.register_source('producer/8',
                          lambda: (_ for _ in ()).throw(OSError('x')))
  try:
    scrapes = metrics.scrape_all()
    assert scrapes['producer/7']['counters']['producer.batches'] == 3
    assert 'error' in scrapes['producer/8']
    assert metrics.snapshot()['counters']['metrics.scrape_error'] == 1
  finally:
    metrics.unregister_source('producer/7')
    metrics.unregister_source('producer/8')


# --------------------------------------------- graftlint metric-registry


def _run_rule(tmp_path, code, registry_src=None, doc=None):
  from graphlearn_tpu.analysis.core import Config, run_lint
  reg = tmp_path / 'regnames.py'
  reg.write_text(registry_src or
                 "REGISTERED_METRICS = frozenset({\n"
                 "    'good.name', 'undoc.name', 'fam.*',\n"
                 "})\n")
  (tmp_path / 'obs.md').write_text(doc if doc is not None else
                                   'Names: `good.name`, `fam.*`.\n')
  mod = tmp_path / 'code.py'
  mod.write_text(code)
  cfg = Config(metrics_registry_module='regnames.py',
               observability_doc='obs.md',
               metrics_exempt_modules=(),
               repo_root=str(tmp_path))
  findings, *_ = run_lint([str(mod), str(reg)], cfg)
  return [f for f in findings if f.rule == 'metric-registry']


def test_metric_rule_literal_registered_ok(tmp_path):
  out = _run_rule(tmp_path, (
      'from graphlearn_tpu import metrics\n'
      'def f(x):\n'
      "  metrics.inc('good.name')\n"
      "  metrics.observe(f'fam.{x}', 1.0)\n"))
  assert [f for f in out if f.relpath == 'code.py'] == []
  # the registry itself is flagged for its undocumented entry
  assert any('undoc.name' in f.message and f.relpath == 'regnames.py'
             for f in out)


def test_metric_rule_flags_unregistered_computed_and_shim(tmp_path):
  out = _run_rule(tmp_path, (
      'from graphlearn_tpu import metrics\n'
      'from graphlearn_tpu.utils.trace import counter_inc\n'
      'def f(x, name):\n'
      "  metrics.inc('rogue.name')\n"          # unregistered literal
      '  metrics.inc(name)\n'                  # computed
      "  metrics.observe(f'{x}.tail', 1.0)\n"  # headless f-string
      "  counter_inc('rogue.two')\n"           # shim form, unregistered
      "  metrics.inc('undoc.name')\n"))        # registered, undocumented
  msgs = [f.message for f in out if f.relpath == 'code.py']
  assert len(msgs) == 5
  assert sum('not in metrics/' in m for m in msgs) == 2
  assert sum('not a string literal' in m for m in msgs) == 1
  assert sum('matches no <prefix>.*' in m for m in msgs) == 1
  assert sum('missing from' in m for m in msgs) == 1


def test_metric_rule_pragma_suppression(tmp_path):
  out = _run_rule(tmp_path, (
      'from graphlearn_tpu import metrics\n'
      'def f(prefix, k):\n'
      '  # graftlint: allow[metric-registry] caller-chosen prefix\n'
      "  metrics.inc(f'{prefix}.{k}')\n"))
  assert [f for f in out if f.relpath == 'code.py'] == []


@pytest.mark.slow  # tier-1 budget (PR 20): redundant package walk —
# test_analysis.py::TestPackageClean runs ALL rules (this one included)
# over the same tree as the tier-1 zero-findings gate
def test_metric_rule_package_is_clean():
  """The real package passes its own rule (the tier-1 zero-findings
  gate in test_analysis covers all rules; this pins the new one)."""
  from graphlearn_tpu.analysis.core import Config, run_lint
  pkg = os.path.join(REPO, 'graphlearn_tpu')
  findings, *_ = run_lint([pkg], Config())
  assert [f for f in findings if f.rule == 'metric-registry'] == []


# ----------------------------------------------- graftlint span-registry


def _run_span_rule(tmp_path, code, registry_src=None, doc=None):
  from graphlearn_tpu.analysis.core import Config, run_lint
  reg = tmp_path / 'regnames.py'
  reg.write_text(registry_src or
                 "REGISTERED_SPANS = frozenset({\n"
                 "    'good.span', 'undoc.span',\n"
                 "})\n")
  (tmp_path / 'obs.md').write_text(doc if doc is not None else
                                   'Spans: `good.span`.\n')
  mod = tmp_path / 'code.py'
  mod.write_text(code)
  cfg = Config(metrics_registry_module='regnames.py',
               observability_doc='obs.md',
               metrics_exempt_modules=(),
               repo_root=str(tmp_path))
  findings, *_ = run_lint([str(mod), str(reg)], cfg)
  return [f for f in findings if f.rule == 'span-registry']


def test_span_rule_literal_registered_ok(tmp_path):
  out = _run_span_rule(tmp_path, (
      'from graphlearn_tpu.metrics import spans\n'
      'def f():\n'
      "  with spans.span('good.span'):\n"
      "    spans.end(spans.begin('good.span'))\n"
      "    spans.emit('good.span', dur_ms=1.0)\n"))
  assert [f for f in out if f.relpath == 'code.py'] == []
  # the registry itself is flagged for its undocumented entry
  assert any('undoc.span' in f.message and f.relpath == 'regnames.py'
             for f in out)


def test_span_rule_flags_unregistered_computed_and_undocumented(tmp_path):
  out = _run_span_rule(tmp_path, (
      'from graphlearn_tpu.metrics import spans\n'
      'def f(name):\n'
      "  spans.begin('rogue.span')\n"       # unregistered literal
      '  spans.span(name)\n'                # computed
      "  spans.emit('undoc.span')\n"))      # registered, undocumented
  msgs = [f.message for f in out if f.relpath == 'code.py']
  assert len(msgs) == 3
  assert sum('not in metrics/registry_names.py' in m for m in msgs) == 1
  assert sum('not a string literal' in m for m in msgs) == 1
  assert sum('missing from' in m for m in msgs) == 1


@pytest.mark.slow  # tier-1 budget (PR 19): span-rule package walk —
# the metric-rule package-clean test stays the tier-1 registry rep
def test_span_rule_pragma_and_package_clean(tmp_path):
  out = _run_span_rule(tmp_path, (
      'from graphlearn_tpu.metrics import spans\n'
      'def f(kind):\n'
      '  # graftlint: allow[span-registry] caller-chosen name\n'
      '  spans.begin(kind)\n'))
  assert [f for f in out if f.relpath == 'code.py'] == []
  from graphlearn_tpu.analysis.core import Config, run_lint
  pkg = os.path.join(REPO, 'graphlearn_tpu')
  findings, *_ = run_lint([pkg], Config())
  assert [f for f in findings if f.rule == 'span-registry'] == []
