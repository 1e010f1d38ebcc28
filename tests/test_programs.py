"""Program observatory + correlated spans (PR 8, metrics/programs.py +
metrics/spans.py).

Unit layer: compile/retrace detection with signature diffs, cost
attribution, the retrace_budget guard rail, span lifecycle/propagation
and the JSONL trails (schema-checked by metrics/logcheck.py).

Acceptance layer:
  * serving p50/p99 derived from request SPAN durations agrees with the
    serving.total_ms histogram within one log-bucket ratio;
  * one serving request over the `serve` RPC yields a single joinable
    span tree spanning the client and server sides, recoverable from
    GLT_SPAN_LOG + scrape_all() by request id alone;
  * flight records carry run_id and the per-epoch `programs` field.
"""
import json
import os
import time
import warnings

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu import metrics
from graphlearn_tpu.metrics import flight, logcheck, programs, spans
from graphlearn_tpu.metrics.programs import (RetraceBudgetExceeded,
                                             diff_signatures,
                                             signature_of)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
  # the observatory and span ring are process-global: scope each test
  # to its own deltas, and never inherit a strict/cost env
  monkeypatch.delenv('GLT_STRICT', raising=False)
  monkeypatch.delenv('GLT_PROGRAM_COST', raising=False)
  monkeypatch.delenv('GLT_SPAN_LOG', raising=False)
  yield


# ----------------------------------------------------------- observatory


def test_instrument_detects_compiles_and_diffs_signatures():
  import jax
  import jax.numpy as jnp
  fn = programs.instrument(jax.jit(lambda x: x * 2), 'test.unit')
  c0 = programs.compile_count('test.unit')
  fn(jnp.ones((4,), jnp.float32))
  fn(jnp.ones((4,), jnp.float32))          # cache hit: dispatch only
  assert programs.compile_count('test.unit') - c0 == 1
  assert programs.last_compile('test.unit').diff == 'first compile'
  fn(jnp.ones((4,), jnp.bfloat16))         # dtype drift: retrace
  assert programs.compile_count('test.unit') - c0 == 2
  ev = programs.last_compile('test.unit')
  assert ev.index >= 1
  assert 'float32[4]' in ev.diff and 'bfloat16[4]' in ev.diff
  assert ev.diff.startswith('arg 0:')
  # dispatch counting includes the compiling calls
  assert programs.default_program_registry() \
      .dispatch_count('test.unit') >= 3


def test_signature_diff_shapes_and_statics():
  a = signature_of((np.ones((8, 4), np.float32), 7), {})
  b = signature_of((np.ones((16, 4), np.float32), 7), {})
  d = diff_signatures(a, b)
  assert 'float32[8,4] -> float32[16,4]' in d
  assert diff_signatures(a, a).startswith('signature unchanged')
  assert diff_signatures(None, a) == 'first compile'
  c = signature_of((np.ones((8, 4), np.float32), 9), {})
  assert 'static:7 -> static:9' in diff_signatures(a, c)


def test_instrument_plain_callable_degrades_to_dispatch_count():
  fn = programs.instrument(lambda x: x + 1, 'test.plain')
  assert fn(1) == 2 and fn(2) == 3
  assert programs.compile_count('test.plain') == 0
  assert programs.default_program_registry() \
      .dispatch_count('test.plain') == 2


def test_retrace_budget_warns_without_strict_and_raises_with(monkeypatch):
  import jax
  import jax.numpy as jnp
  fn = programs.instrument(jax.jit(lambda x: x + 1), 'test.budget')
  fn(jnp.ones((2,)))
  with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter('always')
    with programs.retrace_budget('test.budget', 0):
      fn(jnp.ones((3,)))
  assert len(w) == 1 and 'retrace budget exceeded' in str(w[0].message)
  assert 'last retrace' in str(w[0].message)
  monkeypatch.setenv('GLT_STRICT', '1')
  with pytest.raises(RetraceBudgetExceeded, match='test.budget'):
    with programs.retrace_budget('test.budget', 0):
      fn(jnp.ones((4,)))
  # within budget: no warning, no raise
  with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter('always')
    with programs.retrace_budget('test.budget', 1):
      fn(jnp.ones((5,)))
  assert not w


def test_cost_attribution_once_per_executable(monkeypatch):
  import jax
  import jax.numpy as jnp
  monkeypatch.setenv('GLT_PROGRAM_COST', '1')
  fn = programs.instrument(jax.jit(lambda x: x @ x), 'test.cost')
  fn(jnp.ones((16, 16), jnp.float32))
  ev = programs.last_compile('test.cost')
  assert ev.cost and 'error' not in ev.cost
  assert ev.cost['flops'] > 0
  assert ev.cost['peak_hbm_bytes'] >= 0
  agg = programs.aggregate()
  assert agg['program_flops_total'] and agg['program_flops_total'] > 0
  assert agg['compile_count'] >= 1
  # steady state captures nothing new (cost is once per executable)
  n_events = len(programs.default_program_registry().events('test.cost'))
  fn(jnp.ones((16, 16), jnp.float32))
  assert len(programs.default_program_registry()
             .events('test.cost')) == n_events


# ----------------------------------------------------------------- spans


def test_span_nesting_and_ids():
  with spans.new_trace() as tid:
    with spans.span('epoch.run', emitter='test') as root:
      assert spans.current() == (tid, root.span_id)
      with spans.span('epoch.chunk', k=4):
        pass
  rows = spans.export(trace=tid)
  assert [r['name'] for r in rows] == ['epoch.chunk', 'epoch.run']
  chunk, run = rows
  assert chunk['parent'] == run['span'] and run['parent'] is None
  assert chunk['trace'] == run['trace'] == tid
  assert run['run'] == spans.run_id()
  assert chunk['attrs']['k'] == 4
  tree = spans.build_tree(rows)
  assert tree['roots'] == [run['span']] and not tree['orphans']


def test_span_adopt_and_wire_context():
  ctx = {'trace': 'remotetrace', 'span': 'remotespan'}
  with spans.adopt(ctx):
    assert spans.wire_context() == ctx
    with spans.span('rpc.server.handle', func='x') as tok:
      assert tok.trace == 'remotetrace'
      assert tok.parent == 'remotespan'
  # context restored; a fresh span joins the process run again
  assert spans.current() == (None, None)
  assert spans.wire_context()['trace'] == spans.run_id()


def test_span_log_jsonl_and_schema(tmp_path, monkeypatch):
  path = tmp_path / 'spans.jsonl'
  monkeypatch.setenv('GLT_SPAN_LOG', str(path))
  with spans.new_trace('reqabc') as tid:
    with spans.span('epoch.run', emitter='test'):
      spans.emit('serving.queue', dur_ms=1.25)
  rows = spans.read_log(str(path))
  assert {r['name'] for r in rows} == {'epoch.run', 'serving.queue'}
  assert all(r['trace'] == tid for r in rows)
  # every line passes the logcheck schema (the lint.sh contract)
  assert logcheck.check_file(str(path)) == []
  for r in rows:
    assert logcheck.validate_span(r) == []
  # garbage tolerance mirrors flight.read_records
  with open(path, 'a') as fh:
    fh.write('not json\n')
  assert len(spans.read_log(str(path))) == 2


def test_attached_spans_enter_the_profiler_timeline_as_glt_names(
    monkeypatch):
  """An attached span enters and leaves ``TraceAnnotation('glt.' +
  name)`` on its thread; cross-thread (attach=False) and retroactive
  (emit) spans have no thread interval and stay ring-only."""
  import jax
  log = []

  class Recorder:

    def __init__(self, name):
      self.name = name

    def __enter__(self):
      log.append(('enter', self.name))

    def __exit__(self, *exc):
      log.append(('exit', self.name))

  monkeypatch.setattr(jax.profiler, 'TraceAnnotation', Recorder)
  with spans.span('epoch.run', emitter='test'):
    with spans.span('epoch.chunk', k=2):
      pass
    spans.end(spans.begin('serving.request', attach=False))
    spans.emit('serving.queue', dur_ms=1.0)
  assert log == [('enter', 'glt.epoch.run'), ('enter', 'glt.epoch.chunk'),
                 ('exit', 'glt.epoch.chunk'), ('exit', 'glt.epoch.run')]
  # a span that dies by exception still leaves the timeline
  with pytest.raises(KeyError):
    with spans.span('epoch.hook', hook='ack'):
      raise KeyError('boom')
  assert log[-2:] == [('enter', 'glt.epoch.hook'),
                      ('exit', 'glt.epoch.hook')]


def test_build_tree_flags_orphans_and_dedupes():
  a = spans.end(spans.begin('epoch.run', attach=False, trace='t1'))
  orphan = dict(a, span='zz-1', parent='never-recorded', name='epoch.chunk')
  tree = spans.build_tree([a, a, orphan])     # duplicate collapses
  assert len(tree['spans']) == 2
  assert tree['orphans'] == ['zz-1']


def test_logcheck_rejects_drifted_records(tmp_path):
  bad = tmp_path / 'bad.jsonl'
  bad.write_text(json.dumps({'kind': 'span', 'schema': 1}) + '\n' +
                 json.dumps({'kind': 'mystery'}) + '\n')
  problems = logcheck.check_file(str(bad))
  assert any('missing field' in p for p in problems)
  assert any('unknown record kind' in p for p in problems)
  assert logcheck.main([str(bad), '-q']) == 1
  assert logcheck.main(['-q']) == 0          # recorder self-check


# -------------------------------------------------- flight + scrape joins


def test_flight_record_carries_run_id_and_programs(tmp_path, monkeypatch):
  import jax
  import jax.numpy as jnp
  monkeypatch.setenv('GLT_RUN_LOG', str(tmp_path / 'run.jsonl'))
  fn = programs.instrument(jax.jit(lambda x: x * 3), 'test.flight')
  tok = flight.epoch_begin()
  fn(jnp.ones((4,)))
  rec = flight.epoch_end(tok, emitter='test', epoch=0, steps=1)
  assert rec['run_id'] == spans.run_id()
  assert rec['programs']['test.flight']['compiles'] == 1
  assert rec['programs']['test.flight']['dispatches'] == 1
  assert rec['programs']['test.flight']['compile_s'] > 0
  assert logcheck.validate_flight_record(rec) == []
  # steady-state epoch: dispatch delta only, no compiles key
  tok = flight.epoch_begin()
  fn(jnp.ones((4,)))
  rec2 = flight.epoch_end(tok, emitter='test', epoch=1, steps=1)
  assert rec2['programs']['test.flight'] == {'dispatches': 1}


def test_scrape_all_carries_run_id_and_spans():
  with spans.span('epoch.run', emitter='scrape-test'):
    pass
  scr = metrics.scrape_all()
  local = next(v for k, v in scr.items() if 'error' not in v)
  assert local['run_id'] == spans.run_id()
  names = [r['name'] for r in local['spans']]
  assert 'epoch.run' in names
  # merge still works with the extra keys present
  merged = metrics.merge_scrape(scr)
  assert 'counters' in merged


# ------------------------------------------------- serving span acceptance


def _store(n=30, f=4):
  from graphlearn_tpu.serving.store import EmbeddingStore
  emb = np.arange(n * f, dtype=np.float32).reshape(n, f)
  return EmbeddingStore(emb, num_nodes=n), emb


def test_serving_span_percentiles_match_histogram():
  """Acceptance: p50/p99 derived from serving.request SPAN durations
  agrees with the serving.total_ms histogram within one log-bucket
  ratio (10^0.25 ~ 1.78x) — the two surfaces measure the same requests
  through independent code paths."""
  from graphlearn_tpu.serving.engine import ServingEngine
  store, emb = _store()
  metrics.reset('serving')
  spans.reset()
  with ServingEngine(store, buckets=(8,), max_wait_ms=0.5) as eng:
    for i in range(40):
      eng.lookup(np.arange(1 + (i % 7)))
  durs = np.array([r['dur_ms'] for r in spans.export()
                   if r['name'] == 'serving.request'])
  assert durs.shape[0] == 40
  pct = metrics.histogram('serving.total_ms').percentiles()
  assert metrics.histogram('serving.total_ms').count == 40
  bucket_ratio = 10 ** 0.25 * 1.05      # one log bucket + fp slack
  for q, key in ((50, 'p50'), (99, 'p99')):
    span_q = float(np.percentile(durs, q))
    hist_q = float(pct[key])
    ratio = max(span_q, hist_q) / max(min(span_q, hist_q), 1e-9)
    assert ratio <= bucket_ratio, (key, span_q, hist_q)


def test_serve_rpc_yields_joinable_cross_process_span_tree(
    tmp_path, monkeypatch):
  """Acceptance: ONE serving request over the `serve` RPC produces a
  single joinable span tree spanning the client and server sides —
  rpc.client.request -> rpc.server.handle -> serving.request ->
  {queue, batch -> compute, respond} — recoverable from GLT_SPAN_LOG +
  scrape_all() by request id ALONE (no shared state beyond the id)."""
  from graphlearn_tpu.distributed.dist_server import DistServer
  from graphlearn_tpu.distributed.rpc import RpcClient, RpcServer
  from graphlearn_tpu.serving.engine import ServingEngine
  span_log = tmp_path / 'spans.jsonl'
  monkeypatch.setenv('GLT_SPAN_LOG', str(span_log))
  store, emb = _store()
  server = DistServer(None)
  engine = ServingEngine(store, buckets=(8,), max_wait_ms=0.5).start()
  server.register_serving_engine(engine)
  rpc = RpcServer(handlers={'serve': server.serve,
                            'get_metrics': server.get_metrics})
  client = RpcClient()
  client.add_target(0, rpc.host, rpc.port)
  try:
    with spans.new_trace() as req_id:
      rows = client.request_sync(0, 'serve', np.array([3, 4, 5]),
                                 idempotent=True)
    np.testing.assert_allclose(rows, emb[[3, 4, 5]], rtol=1e-6)

    # the dispatcher thread finishes its respond/end bookkeeping just
    # after set_result unblocks the RPC — wait for the request span
    deadline = time.monotonic() + 5
    want = {'rpc.client.request', 'rpc.server.handle', 'serving.request',
            'serving.queue', 'serving.batch', 'serving.compute',
            'serving.respond'}
    while time.monotonic() < deadline:
      have = {r['name'] for r in spans.export(trace=req_id)}
      if want <= have:
        break
      time.sleep(0.01)

    # recovery by request id alone: the JSONL + the scrape
    scr = metrics.scrape_all()
    collected = spans.dedupe(
        spans.from_scrape(scr, trace=req_id) +
        [r for r in spans.read_log(str(span_log))
         if r['trace'] == req_id])
    tree = spans.build_tree(collected)
    assert {r['name'] for r in collected} == want
    assert not tree['orphans']
    assert len(tree['roots']) == 1
    root = tree['spans'][tree['roots'][0]]
    assert root['name'] == 'rpc.client.request'

    def child_names(span_id):
      return {tree['spans'][c]['name']
              for c in tree['children'].get(span_id, ())}

    handle = [r for r in collected if r['name'] == 'rpc.server.handle']
    assert len(handle) == 1 and handle[0]['parent'] == root['span']
    request = [r for r in collected if r['name'] == 'serving.request']
    assert len(request) == 1
    assert request[0]['parent'] == handle[0]['span']
    assert child_names(request[0]['span']) >= {'serving.queue',
                                               'serving.batch',
                                               'serving.respond'}
    batch = [r for r in collected if r['name'] == 'serving.batch'][0]
    assert child_names(batch['span']) == {'serving.compute'}
  finally:
    engine.stop()
    client.close()
    rpc.shutdown()
