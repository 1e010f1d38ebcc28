"""``local_draw_tiles_per_step``: the engagement counter of the shard-local
draws' tile loop, read off the timeline as ``row_exchange_tiles_per_step``
reads the owners' — on hand-made events and on the recorded traces of
programs that draw their blocks in one piece.

Like the other files here these test the yardstick: an op is counted where
it sits directly in a shard-local draw's tile body under
``glt.sample/hop<h>/draw``, a loop's count is its most frequent op
instance's, the two capacities' loops and the hops add up, chips are
averaged, and a program with no such scope reads None, never 0.
"""
import json
import os

import pytest

from perfbench import mesh_parts_reduce as parts, run, trace_reduce
from perfbench.layer_metrics import local_draw_tiles_per_step as tiles

FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
CELL = 'sage-papers.mesh-exact'


def _draw(hop, branch='branch_1_fun'):
  """The path to a shard-local draw's tile body in hop ``hop``."""
  return ('glt.sample', 'cond', branch, f'hop{hop}', 'draw',
          'jit(uniform_sample_local)', 'while', 'body', 'tile')


@pytest.mark.parametrize('path, want', [
    (_draw(2) + ('gather',), ('glt.sample/hop2/draw', 'branch_1_fun')),
    (_draw(1) + ('rows', 'gather'), ('glt.sample/hop1/draw', 'branch_1_fun')),
    (_draw(2, 'branch_0_fun') + ('dynamic_update_slice',),
     ('glt.sample/hop2/draw', 'branch_0_fun')),
    # the weighted draw, and no cond (the full-width posture, P = 1)
    (('glt.sample', 'hop2', 'draw', 'jit(weighted_sample_local)', 'while',
      'body', 'tile', 'select_n'), ('glt.sample/hop2/draw',)),
    # the row lookup's halvings and the weighted bisection, nested inside
    # the body: once a round
    (_draw(2) + ('rows', 'while', 'body', 'closed_call', 'gather'), None),
    (_draw(2) + ('while', 'body', 'gather'), None),
    # the parts around the loop, and the one-piece draw
    (('glt.sample', 'cond', 'branch_1_fun', 'hop2', 'draw',
      'jit(uniform_sample_local)', 'rows', 'gather'), None),
    (('glt.sample', 'cond', 'branch_1_fun', 'hop2', 'exchange',
      'all_to_all'), None),
    # the one-chip draw's tiles are draw_tiles_per_step's
    (('glt.sample', 'hop2', 'draw', 'jit(uniform_sample)', 'while', 'body',
      'tile', 'gather'), None),
    # the owners' bounded lookup is row_exchange_tiles_per_step's
    (('glt.collate', 'exchange', 'cond', 'branch_1_fun', 'while', 'body',
      'lookup', 'tile', 'gather'), None),
    ((), None)])
def test_an_op_is_counted_where_it_sits_directly_in_a_tile_body(path, want):
  assert tiles.tile_loop(path) == want
  if want is not None:                 # and its time stays the draw's
    assert parts.part_of(path) == (want[0], parts.DRAW_ROWS if 'rows' in path
                                   else parts.UNSPLIT)


def _op(chip, name, *path):
  return dict(lane=trace_reduce.OP_LANE, chip=f'/device:TPU:{chip}',
              name=name, ts=0.0, dur=1.0, args={'tf_op': '/'.join(path)})


def test_branches_and_hops_add_up_instances_do_not_chips_are_averaged(
    capsys):
  device = []
  for chip, n2, n1 in ((0, 21, 6), (1, 23, 5)):
    device += [_op(chip, 'fusion.1', *_draw(2), 'gather')] * n2
    # a short op of the same body, some executions cut from the trace
    device += [_op(chip, 'fusion.2', *_draw(2), 'rows', 'add')] * (n2 - 3)
    # the nested halvings: four rounds a tile, never counted
    device += [_op(chip, 'fusion.3', *_draw(2), 'rows', 'while', 'body',
                   'closed_call', 'gather')] * (4 * n2)
    device += [_op(chip, 'fusion.4', *_draw(1), 'gather')] * n1
  # chip 1 fell back to the full width in hop 2 of one step: its own
  # loop, 2 tiles
  device += [_op(1, 'fusion.9', *_draw(2, 'branch_0_fun'), 'gather')] * 2
  device.append(dict(_op(0, 'jit_body', *_draw(2), 'gather'),
                     lane=trace_reduce.PROGRAM_LANE))
  run_ = dict(scan=dict(device=device, host=[], steps=2))
  assert tiles.tile_runs(device) == {'/device:TPU:0': 27,
                                     '/device:TPU:1': 30}
  assert tiles.tile_runs(device, 'glt.sample/hop1/draw') == {
      '/device:TPU:0': 6, '/device:TPU:1': 5}
  assert tiles.read(run_) == pytest.approx((27 / 2 + 30 / 2) / 2)
  lines = [l for l in capsys.readouterr().out.splitlines()
           if 'local_draw_tiles' in l]
  assert len(lines) == 1
  said = json.loads(lines[0][len('perfbench: '):])['local_draw_tiles']
  assert said['tiles_per_step_by_hop'] == pytest.approx({
      'glt.sample/hop1/draw': (6 + 5) / 2 / 2,
      'glt.sample/hop2/draw': (21 + 25) / 2 / 2})
  assert said['tiles_per_step_by_chip'] == {'/device:TPU:0': 13.5,
                                            '/device:TPU:1': 15.0}


@pytest.mark.parametrize('trace, steps', [
    ('trace_v5e_mesh_tiles_cut.json', 1),  # four chips, draws in one piece
    ('trace_v5e_mesh_parts_cut.json', 1),  # the same, before the lookup tiled
    ('trace_v5e_mesh_cut.json', 1),        # four chips, no parts named
    ('trace_v5e_tiled_cut.json', 2),       # one chip: the one-chip draw's
    ('trace_v5e_cut.json', 2)])            # one chip, no mesh chunk
def test_the_reader_finds_nothing_where_no_block_is_drawn_in_tiles(
    trace, steps, capsys):
  device, host = trace_reduce.load(os.path.join(FIX, trace))
  run_ = dict(scan=dict(device=device, host=host, steps=steps))
  assert tiles.tile_runs(device) == {}
  assert tiles.read(run_) is None
  assert 'local_draw_tiles' not in capsys.readouterr().out


def test_the_entry_the_benchmark_lists_is_the_readers():
  """``BENCHMARK.json`` lists the reader for the mesh cell alone, with the
  reader's ``LAYER`` / ``UNIT`` / ``MOVES``."""
  entry = dict(name='local_draw_tiles_per_step', unit=tiles.UNIT,
               better='lower', source='device_trace', layer=tiles.LAYER,
               moves=tiles.MOVES, workloads=[CELL])
  assert (tiles.LAYER, tiles.UNIT, tiles.MOVES) == ('sampling', 'count',
                                                    'seeds_per_s')
  with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  assert CELL in [w['name'] for w in bench['workloads']]
  assert entry['layer'] in {m['layer'] for m in bench['per_layer']
                            if m['name'] != entry['name']}
  assert [m for m in bench['per_layer']
          if m['name'] == entry['name']] == [entry]
