"""PR 30's four readers of the PER-BATCH loop's layer clock:
``step_{sample,collate,train,unscoped}_ms`` over ``perfbench/step_reduce.py``.

Beside ``test_scope_metrics.py``, whose helpers it shares. Under the ``step``
executor the layers are separate programs, so the reduction is over every op
of the slice, not over one program: tested on a slice written out by hand
(two batches of three programs and a key fold, one ``while`` with a body) and
on PR 25's recorded per-batch trace, which names no scope and must read as
nothing, never 0.
"""
import json

import pytest

from perfbench import trace_reduce
from test_scope_metrics import _read, _run_from

STEP_READERS = ['step_sample_ms', 'step_collate_ms', 'step_train_ms',
                'step_unscoped_ms']

# (program, [(op, tf_op, start us within the program, dur us)]) of one batch
_BATCH = [
    ('jit__threefry_fold_in(1)', [('fusion', '', 0, 2)]),
    ('jit_sample_merge_capped(2)', [
        ('fusion.1', 'jit(f)/glt.sample/hop1/draw/gather', 0, 30),
        ('sort.3', 'jit(f)/glt.sample/hop1/induce/sort', 30, 20),
        # a while counts its own overhead, its body counts once
        ('while.2', 'jit(f)/glt.sample/hop2/draw/tiles/while', 50, 50),
        ('fusion.4', 'jit(f)/glt.sample/hop2/draw/tiles/while/body/tile/'
                     'gather', 52, 40)]),
    ('jit_collate_batch(3)', [
        ('copy.2', '', 0, 37),       # the boundary's relayout: no scope
        ('fusion.5', 'jit(g)/glt.collate/jit(gather_rows)/gather', 37, 18)]),
    ('jit_train_step(4)', [
        ('fusion.7', 'jit(h)/glt.train/fwd_bwd/dot_general', 0, 100),
        ('fusion.8', 'jit(h)/glt.train/update/add', 100, 1)]),
]
_WANT_US = {'step_sample_ms': 30 + 20 + 10 + 40, 'step_collate_ms': 18,
            'step_train_ms': 101, 'step_unscoped_ms': 2 + 37}


def _write_trace(path, batches):
  ev = [dict(ph='M', pid=1, name='process_name', args=dict(
      name='/device:TPU:0')),
        dict(ph='M', pid=1, tid=1, name='thread_name', args=dict(
            name=trace_reduce.PROGRAM_LANE)),
        dict(ph='M', pid=1, tid=2, name='thread_name', args=dict(
            name=trace_reduce.OP_LANE)),
        dict(ph='M', pid=2, name='process_name', args=dict(
            name='/host:CPU')),
        dict(ph='M', pid=2, tid=1, name='thread_name', args=dict(
            name='python3'))]
  t = 100.0
  for _ in range(batches):
    t0 = t
    for program, ops in _BATCH:
      end = max(s + d for _, _, s, d in ops)
      ev.append(dict(ph='X', pid=1, tid=1, name=program, ts=t, dur=end))
      for op, tf_op, s, d in ops:
        e = dict(ph='X', pid=1, tid=2, name=op, ts=t + s, dur=d)
        if tf_op:
          e['args'] = dict(tf_op=tf_op)
        ev.append(e)
      t += end + 5.0                 # the device idles between programs
    ev.append(dict(ph='X', pid=2, tid=1, name='perfbench.step', ts=t0,
                   dur=t - t0))
  with open(path, 'w') as f:
    json.dump(dict(traceEvents=ev), f)


def test_the_step_readers_add_up_to_the_slices_busy_time(tmp_path, capsys):
  path = str(tmp_path / 'step.trace.json')
  _write_trace(path, batches=2)
  run_ = _run_from(path, 2)
  got = {name: _read(name, run_) for name in STEP_READERS}
  for name, us in _WANT_US.items():
    assert got[name] == pytest.approx(us / 1e3, rel=1e-9), name
  assert sum(got.values()) == pytest.approx(
      1e3 * run_['scan']['busy_s'] / 2, rel=1e-9)
  lines = [json.loads(l[len('perfbench: '):])['step_reduce']
           for l in capsys.readouterr().out.splitlines()
           if l.startswith('perfbench: ') and 'step_reduce' in l]
  assert len(lines) == 1             # reduced once, said once
  assert lines[0]['step_sub_scopes_ms']['glt.sample/hop2/draw'] == \
      pytest.approx(0.05)
  assert list(lines[0]['step_unscoped_ops_ms']) == ['copy', 'fusion']
  # the scanned cells' readers look for the chunk program and find none
  assert _read('scan_sample_ms', run_) is None


@pytest.mark.parametrize('name', STEP_READERS)
def test_a_program_without_scopes_reads_as_nothing_never_zero(name, capsys):
  # PR 25's recorded trace: per-batch programs that named no scope
  run_ = _run_from('trace_v5e_cut.json', 2)
  assert _read(name, run_) is None
  assert 'step_reduce' not in capsys.readouterr().out
