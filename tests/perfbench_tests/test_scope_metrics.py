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
  trace standing in for the traced slice."""
  device, host = trace_reduce.load(os.path.join(FIX, trace_file))
  busy_s, window_s, _ = trace_reduce.busy(device)
  slice_ = dict(device=device, host=host, steps=steps, busy_s=busy_s,
                window_s=window_s)
  return dict(cell=_Cell, traffic={}, window=dict(steps=0, wall_s=0.0),
              peaks=dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12),
              counts=dict(nodes=[1024.0, 10700.0, 39000.0, 43200.0],
                          edges=[], buffer_rows=139776),
              scan=slice_)


def _while_dur_seconds(device):
  return sum(e['dur'] for e in device if e['lane'] == trace_reduce.OP_LANE
             and e['name'].split('.')[0] == 'while') / 1e6


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
  # body twice, self time counts it once
  while_dur = _while_dur_seconds(device)
  assert while_dur == pytest.approx(want['while_dur_seconds'], rel=1e-9)
  assert loose['while'] == pytest.approx(want['while_self_seconds'],
                                         rel=1e-6)
  assert loose['while'] < 0.02 * while_dur
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


def test_breakdown_device_ops_are_self_time_by_scope_and_op_class():
  """``breakdown.device_ops``: what the next issue's writer reads. A
  ``while`` counts its own overhead, not its body again, so the list adds
  up to no more than the slice's busy time; each entry names its scope."""
  with open(os.path.join(FIX, 'trace_v5e_scan_cut.expected.json')) as f:
    want = json.load(f)
  device, _ = trace_reduce.load(os.path.join(FIX, 'trace_v5e_scan_cut.json'))
  ops = scope_reduce.device_ops(device)
  assert 0 < len(ops) <= 10
  seconds = [s for _, s in ops]
  assert seconds == sorted(seconds, reverse=True)
  busy_s = trace_reduce.busy(device)[0]
  assert sum(seconds) <= busy_s * (1 + 1e-9)
  assert sum(seconds) > 0.5 * busy_s          # the top ten say something
  scopes = {name.split(':')[0] for name, _ in ops}
  assert scopes <= set(want['scope_seconds']) | {scope_reduce.UNSCOPED}
  assert any(s.startswith('glt.train') for s in scopes)
  everything = dict(scope_reduce.device_ops(device, top=10 ** 6))
  assert sum(everything.values()) == pytest.approx(busy_s, rel=1e-6)
  assert everything['unscoped:while'] == pytest.approx(
      want['while_self_seconds'], rel=1e-6)
  # the sum of durations the ledger's PR 27 lines show repeats the body
  assert _while_dur_seconds(device) > 0.9 * busy_s


def test_breakdown_idle_gaps_are_named_by_the_programs_innermost_span():
  span = lambda name, ts, dur: dict(name=name, ts=ts, dur=dur)
  host = [span('perfbench.run_epoch', 0, 1000),
          span('glt.epoch.run', 10, 900), span('glt.epoch.chunk', 100, 300),
          span('perfbench.host_fetch', 1000, 500),
          span('$other.thread', 0, 5000)]
  gaps = [(150, 160), (500, 560), (1100, 1400), (2000, 2001), (2, 6)]
  assert scope_reduce.idle_gaps(gaps, host, top=4) == [
      ['host_fetch', pytest.approx(300e-6)],
      ['glt.epoch.run', pytest.approx(60e-6)],
      ['glt.epoch.chunk', pytest.approx(10e-6)],
      ['run_epoch', pytest.approx(4e-6)]]
  assert scope_reduce.idle_gaps(gaps, host)[-1] == [
      trace_reduce.NO_SPAN, pytest.approx(1e-6)]


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
