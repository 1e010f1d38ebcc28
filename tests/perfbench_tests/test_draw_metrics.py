"""PR 27's two readers of the draw inside ``glt.sample``: ``scan_draw_ms``
(self time under ``glt.sample/hop<h>/draw``) and ``draw_tiles_per_step``
(executions of the tiled draw's body), on the recorded v5e traces.

Beside ``test_scope_metrics.py``, whose helpers it shares: the yardstick is
tested on hand-cut traces of one scanned chunk — PR 26's program, which
drew each hop in one piece, and PR 27's, which draws hops 1 and 2 tile by
tile — and a program without scopes reads as nothing, never 0.
"""
import json
import os

import pytest

from test_scope_metrics import FIX, _read, _run_from

DRAW_READERS = ['scan_draw_ms', 'draw_tiles_per_step']


@pytest.mark.parametrize('fixture', ['trace_v5e_scan_cut',
                                     'trace_v5e_tiled_cut'])
def test_the_draw_readers_on_recorded_v5e_chunks(fixture, capsys):
  """``scan_draw_ms`` is the draws' self time summed over the hops, and
  ``draw_tiles_per_step`` the executions of the tile body: nothing (never
  0) on PR 26's program; on PR 27's, per hop, the trip count of the
  draw's own ``while``."""
  with open(os.path.join(FIX, fixture + '.expected.json')) as f:
    want = json.load(f)
  run_ = _run_from(fixture + '.json', want['steps'])
  draw_s = {k.split('/')[1]: s for k, s in want['scope_seconds'].items()
            if k.endswith('/draw')}
  assert sorted(draw_s) == ['hop0', 'hop1', 'hop2']
  assert _read('scan_draw_ms', run_) == pytest.approx(
      1e3 * sum(draw_s.values()) / want['steps'], rel=1e-9)
  tiles = want.get('tile_bodies_run')      # {hop: count}, PR 27's program
  got = _read('draw_tiles_per_step', run_)
  if tiles is None:
    assert got is None
  else:
    assert 'hop0' not in tiles             # a seed batch is not tiled
    assert got == pytest.approx(sum(tiles.values()) / want['steps'],
                                rel=1e-12)
  lines = [json.loads(l[len('perfbench: '):])['draw_reduce']
           for l in capsys.readouterr().out.splitlines()
           if l.startswith('perfbench: ') and 'draw_reduce' in l]
  assert len(lines) == 1                   # once, shared by both readers
  assert lines[0]['draw_ms_by_hop'] == pytest.approx(
      {h: 1e3 * s / want['steps'] for h, s in draw_s.items()}, rel=1e-9)
  assert lines[0]['tiles_per_step_by_hop'] == pytest.approx(
      {h: n / want['steps'] for h, n in (tiles or {}).items()})


@pytest.mark.parametrize('name', DRAW_READERS)
def test_a_program_without_scopes_reads_as_nothing_never_zero(name, capsys):
  # PR 25's recorded trace: per-batch programs that named no scope
  run_ = _run_from('trace_v5e_cut.json', 2)
  assert _read(name, run_) is None
  assert 'draw_reduce' not in capsys.readouterr().out
