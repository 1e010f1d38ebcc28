"""The layer clock's readers (PR 26): ``perfbench/scope_reduce.py`` and the
six per-layer metrics that read the program's ``glt.*`` scopes and spans.

Like ``test_perfbench.py`` these test the yardstick: self time per scope on
a hand-cut recorded v5e trace of one scanned chunk, that a program without
scopes reads as nothing (never 0), and that the program's spans reach a
profiler trace under the names the readers look for.
"""
import importlib
import json
import os
import time

import pytest

from perfbench import run, scope_reduce, trace_reduce

FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
READERS = ['scan_sample_ms', 'scan_collate_ms', 'scan_train_ms',
           'scan_unscoped_ms', 'scan_collate_roofline', 'host_gap_ms']


class _Cell:

  class feat:
    shape = (1000, 100)

    class dtype:
      itemsize = 4


def _run_from(trace_file, steps):
  """What ``run.traced_phase`` hands the readers, built from one recorded
  trace standing in for both slices."""
  device, host = trace_reduce.load(os.path.join(FIX, trace_file))
  busy_s, window_s, _ = trace_reduce.busy(device)
  slice_ = dict(device=device, host=host, steps=steps, busy_s=busy_s,
                window_s=window_s)
  return dict(cell=_Cell, traffic={}, window=dict(steps=0, wall_s=0.0),
              peaks=dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12),
              counts=dict(nodes=[1024.0, 10700.0, 39000.0, 43200.0],
                          edges=[], buffer_rows=139776),
              scan=slice_, step=slice_)


def _read(name, run_):
  return importlib.import_module(
      f'perfbench.layer_metrics.{name}').read(run_)


def test_self_time_by_scope_on_a_recorded_v5e_scanned_chunk(capsys):
  with open(os.path.join(FIX, 'trace_v5e_scan_cut.expected.json')) as f:
    want = json.load(f)
  run_ = _run_from('trace_v5e_scan_cut.json', want['steps'])
  device = run_['scan']['device']
  scopes, loose = scope_reduce.by_scope(device, scope_reduce.CHUNK_STEM)
  assert set(scopes) == set(want['scope_seconds'])
  for k, s in want['scope_seconds'].items():
    assert scopes[k] == pytest.approx(s, rel=1e-9), k
  assert list(loose)[:3] == want['top_unscoped_ops']
  # the while wraps every op of the body: summing durations counts the
  # body twice (trace_reduce.op_seconds does), self time counts it once
  ops = trace_reduce.op_seconds(device)
  assert ops['while'] == pytest.approx(want['while_dur_seconds'], rel=1e-9)
  assert loose['while'] == pytest.approx(want['while_self_seconds'],
                                         rel=1e-6)
  assert loose['while'] < 0.02 * ops['while']
  # scopes + unscoped = the union of the chunk program's busy intervals
  chunk_busy = want['chunk_busy_seconds']
  assert sum(scopes.values()) == pytest.approx(chunk_busy, rel=1e-9)
  assert chunk_busy == pytest.approx(trace_reduce.busy(device)[0] -
                                     want['other_programs_seconds'],
                                     rel=1e-6)
  # the six readers on the same trace, and the line scope_reduce prints
  got = {name: _read(name, run_) for name in READERS}
  for name, value in want['metrics'].items():
    assert got[name] == pytest.approx(value, rel=1e-9), name
  four = sum(got[n] for n in READERS[:4])
  assert four == pytest.approx(1e3 * chunk_busy / want['steps'], rel=1e-9)
  lines = [json.loads(l[len('perfbench: '):])
           for l in capsys.readouterr().out.splitlines()
           if l.startswith('perfbench: ')]
  assert len(lines) == 1            # printed once, by the first reader
  line = lines[0]['scope_reduce']
  assert set(line['host_gap_ms_by_span']) == set(want['gap_spans'])
  assert set(line['scan_sub_scopes_ms']) == set(want['scope_seconds'])


@pytest.mark.parametrize('name', READERS)
def test_a_program_without_scopes_reads_as_nothing_never_zero(name, capsys):
  # PR 25's recorded trace: per-batch programs of a program that named no
  # scope and put no span on the profiler's clock (the parent of PR 26)
  run_ = _run_from('trace_v5e_cut.json', 2)
  assert _read(name, run_) is None
  assert 'scope_reduce' not in capsys.readouterr().out


def test_the_programs_spans_reach_the_trace_under_glt_names(tmp_path):
  import jax
  from graphlearn_tpu.metrics import spans
  run.trace_session(jax, str(tmp_path))
  try:
    with spans.span('epoch.run', emitter='test'):
      time.sleep(0.002)
      with spans.span('epoch.chunk', start=0, k=2):
        time.sleep(0.004)
      with spans.span('epoch.hook', hook='ack', start=0):
        time.sleep(0.002)
  finally:
    jax.profiler.stop_trace()
  _, host = trace_reduce.load(str(tmp_path))
  mine = {e['name']: e for e in host if e['name'].startswith('glt.')}
  assert set(mine) == {'glt.epoch.run', 'glt.epoch.chunk', 'glt.epoch.hook'}
  outer, chunk = mine['glt.epoch.run'], mine['glt.epoch.chunk']
  assert outer['ts'] <= chunk['ts'] and \
      chunk['ts'] + chunk['dur'] <= outer['ts'] + outer['dur']
  assert chunk['dur'] >= 4000          # microseconds, the profiler's clock
  # an idle gap is named by the innermost program span open in it
  gaps = [(chunk['ts'] + 1000, chunk['ts'] + 3000),
          (outer['ts'] + 100, outer['ts'] + 1100)]
  assert trace_reduce.label_gaps(gaps, host, prefix='glt.') == [
      ['epoch.chunk', pytest.approx(0.002)],
      ['epoch.run', pytest.approx(0.001)]]
