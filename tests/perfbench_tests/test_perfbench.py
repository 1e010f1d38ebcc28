"""The benchmark's own tests (tier-1 collects them; ``BENCHMARK.json``
lists this directory under ``paths``, so later PRs cannot change them).

They test the yardstick, not the program: the trace reduction on a
recorded trace, the FLOP count against hand arithmetic, a CPU rehearsal of
the whole command at a toy shape, and that ``correct`` comes out false
when the timed path is broken underneath or computed in bfloat16.
"""
import json
import os

import pytest

from perfbench import control, flops, run, trace_reduce

FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
TINY = dict(require_platform='cpu',
            bench_file='perfbench/fixtures/BENCHMARK.tiny.json')


def rehearse(capsys, workload='tiny-sage.tiny-scan', seed=3_000_000_019,
             trace=0, fixtures=TINY, said=None):
  """One run on the CPU, its last line parsed; ``said`` (a dict) gathers
  what the run's earlier ``perfbench:`` lines said."""
  run.main(['--workload', workload, '--seed', str(seed), '--seconds', '0.2',
            '--trace', str(trace)], **fixtures)
  lines = capsys.readouterr().out.strip().splitlines()
  if said is not None:
    for line in lines[:-1]:
      if line.startswith('perfbench: {'):
        said.update(json.loads(line[len('perfbench: '):]))
  return json.loads(lines[-1])


def test_trace_reduction_on_a_recorded_v5e_trace():
  device, host = trace_reduce.load(os.path.join(FIX, 'trace_v5e_cut.json'))
  with open(os.path.join(FIX, 'trace_v5e_cut.expected.json')) as f:
    want = json.load(f)
  lanes = {e['lane'] for e in device}
  assert {trace_reduce.PROGRAM_LANE, trace_reduce.OP_LANE, 'Steps'} <= lanes
  # the Steps lane repeats the same device time: reading it would double it
  assert sum(e['dur'] for e in device if e['lane'] == trace_reduce.OP_LANE
             and not e['name'].startswith('jit_')) / 1e6 == pytest.approx(
                 want['op_seconds_total'], rel=1e-9)
  assert trace_reduce.window_of(host) == pytest.approx(
      tuple(want['host_window_us']))
  busy_s, window_s, gaps = trace_reduce.busy(device,
                                             tuple(want['window_us']))
  assert busy_s == pytest.approx(want['busy_s'], rel=1e-6)
  assert window_s == pytest.approx(want['window_s'], rel=1e-9)
  assert 0 < busy_s < window_s
  labelled = trace_reduce.label_gaps(gaps, host, top=3)
  assert [g[0] for g in labelled] == want['gap_labels']
  assert [g[1] for g in labelled] == pytest.approx(want['gap_seconds'],
                                                   rel=1e-6)


def test_busy_union_counts_nested_and_overlapping_ops_once():
  ev = lambda ts, dur, lane='XLA Ops': dict(
      ts=ts, dur=dur, lane=lane, chip='/device:TPU:0', name='fusion.1')
  device = [ev(0, 100), ev(10, 20), ev(90, 30), ev(200, 50),
            ev(0, 400, lane='Steps')]
  busy_s, window_s, gaps = trace_reduce.busy(device, (0, 400))
  assert busy_s == pytest.approx(170e-6) and window_s == pytest.approx(4e-4)
  assert gaps == [(120, 200), (250, 400)]


def test_flops_by_hand_for_one_sage_and_one_gat_layer():
  # SAGE, 10 output rows, 40 edges, 8 -> 4: two matmuls 2*10*8*4 = 640
  # each forward; an inner layer pays them three times (forward, dW, dX),
  # the first layer twice; the mean is 40*8 adds, forward and backward
  assert flops.sage_layer_flops(10, 40, 8, 4, first=False) == 3 * 1280 + 640
  assert flops.sage_layer_flops(10, 40, 8, 4, first=True) == 2 * 1280 + 640
  # GAT, 30 input rows, 40 edges, 8 -> 2 heads x 4: projection 2*30*8*8 =
  # 3840; attention dots 2*2*30*8 = 960; per edge and head 6 + 2*4 = 14
  assert flops.gat_layer_flops(30, 10, 40, 8, 2, 4, first=False) == \
      3 * 3840 + 2 * (960 + 40 * 2 * 14)
  # a whole model: layer i reads the rows within L-i hops
  model = dict(kind='sage', in_dim=8, hidden=4, out_dim=3, layers=2, heads=1)
  nodes, edges = [2, 5, 9], [6, 20]
  want = (flops.sage_layer_flops(7, 26, 8, 4, True) +
          flops.sage_layer_flops(2, 6, 4, 3, False))
  assert flops.step_flops(model, nodes, edges) == want
  assert flops.collate_bytes(100, 10, 4) == 8000


def test_rehearsal_last_line_parses_names_the_device_and_times_nothing(
    capsys):
  out = rehearse(capsys, trace=1)
  assert list(out)[-1] == 'compared' and out['correct'] is True
  assert out['device']['platform'] == 'cpu' and out['device']['count'] >= 1
  assert out['attempted'] > 0 and out['failed'] == 0
  # off a TPU the measuring path reports no device metric at all
  assert out['metrics'] == {} and 'breakdown' not in out
  assert 'busy_s' not in out['device']
  assert set(out['compared']) == {
      'bad_edges', 'fanout_misses', 'dup_nodes', 'bad_rows', 'overflow',
      'loss_gap_step1', 'loss_gap', 'dparam_gap', 'moment_gap'}


def test_refuses_to_run_without_the_accelerator(capsys):
  with pytest.raises(SystemExit) as e:
    run.main(['--workload', 'tiny-sage.tiny-scan', '--seed', '1',
              '--seconds', '0.2'], **dict(TINY, require_platform='tpu'))
  assert e.value.code not in (0, None)
  assert 'correct' not in capsys.readouterr().out


def _unchanged_state(monkeypatch):
  from graphlearn_tpu.models import train as train_lib
  real = train_lib.make_train_step

  def broken(model, tx, num_classes):
    step, ev = real(model, tx, num_classes)

    def stuck(state, batch):
      _, loss, acc = step(state, batch)
      return state, loss, acc

    return stuck, ev

  monkeypatch.setattr(train_lib, 'make_train_step', broken)


def _half_batch(monkeypatch):
  from graphlearn_tpu.models import train as train_lib
  real = train_lib.make_loss_fn

  def broken(model, num_classes):
    loss_fn = real(model, num_classes)
    return lambda params, b: loss_fn(
        params, dict(b, num_seed_nodes=b['num_seed_nodes'] // 2))

  monkeypatch.setattr(train_lib, 'make_loss_fn', broken)


def _wrong_rows(monkeypatch):
  import jax.numpy as jnp

  from graphlearn_tpu import ops
  real = ops.collate_batch

  def broken(*a, **kw):
    out = real(*a, **kw)
    return dict(out, x=jnp.roll(out['x'], 1, axis=0))

  monkeypatch.setattr(ops, 'collate_batch', broken)


@pytest.mark.parametrize('fault,caught_by', [
    (_unchanged_state, 'dparam_gap'), (_half_batch, 'moment_gap'),
    (_wrong_rows, 'bad_rows')])
def test_a_broken_timed_path_comes_out_not_correct(fault, caught_by,
                                                   monkeypatch, capsys):
  fault(monkeypatch)
  out = rehearse(capsys, seed=77)
  row = out['compared'][caught_by]
  assert out['correct'] is False and row['value'] > row['limit']


def test_the_bfloat16_control_fails_and_the_program_passes(capsys):
  readings = control.main(
      ['--workload', 'tiny-gat.tiny-scan', '--seeds', '1',
       '--control-seeds', '1', '--program-control', '1'], **TINY)
  capsys.readouterr()
  limits = run.load_cell('tiny-gat.tiny-scan', TINY['bench_file'])[-1]
  by = {r['kind']: r for r in readings}
  passes = lambda r: all(r[k] <= limits[k] for k in control.MEASURED)
  assert passes(by['program'])
  assert not passes(by['control_ref_bf16'])
  assert not passes(by['control_program_bf16'])
  assert not passes(by['fault_half_batch'])


BENCH_FILES = ['BENCHMARK.json', TINY['bench_file'],
               'perfbench/fixtures/BENCHMARK.toy.json']


@pytest.mark.parametrize('bench_file', BENCH_FILES)
def test_benchmark_json_names_only_files_that_exist(bench_file):
  """Generic over families: a later PR's cell passes by adding files. What
  holds only for one source or one executor is asked of those alone."""
  with open(os.path.join(run.ROOT, bench_file)) as f:
    bench = json.load(f)
  real = bench_file == 'BENCHMARK.json'
  e2e = {m['name'] for m in bench['end_to_end']}
  if real:
    assert 'setup_s' in e2e
    assert 'traffic_dir' not in bench and 'limits_dir' not in bench
  for m in bench['per_layer']:
    mod = __import__(f'perfbench.layer_metrics.{m["name"]}',
                     fromlist=['read'])
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m['layer'], m['unit'],
                                                m['moves'])
    assert m['moves'] in e2e or not real
  configs = {c['name']: c for c in bench['configs']}
  for w in bench['workloads']:
    _, entry, cfg, traffic, limits = run.load_cell(w['name'], bench_file)
    assert cfg['name'] == entry['config']
    assert cfg['reduced'] == configs[cfg['name']]['reduced']
    # a compared number is one of the generic measured ones, or an exact
    # number of the cell's family, which has the limit 0
    assert all(k in control.MEASURED or v == 0 for k, v in limits.items())
    assert set(control.MEASURED) & set(limits)
    for package, name in (('families', cfg['family']),
                          ('executors', traffic['executor'])):
      homes = [package] if real else [package, 'fixtures']
      assert any(os.path.isfile(os.path.join(run.ROOT, 'perfbench', home,
                                             name + '.py'))
                 for home in homes)
    if traffic['executor'] == 'scan':
      # a scanned chunk keeps no state before its end
      assert traffic['reference_steps'] == traffic['chunk_size']
    if 'ogbn_products' in cfg['source']:
      # the published shape, not cut
      assert cfg['reduced'] == []
      assert cfg['dataset']['num_directed_edges'] == 123_718_280
