"""``row_exchange_tiles_per_step`` (PR 40): the engagement counter of the
owners' bounded lookup, read off the timeline as ``draw_tiles_per_step``
reads the draw's — on hand-made events, on a hand-cut recorded 4-chip v5e
trace of a program that tiles its received blocks, and on the recorded
traces of programs that do not.

Like the other files here these test the yardstick: an op is counted where
it sits directly in a tile body under ``glt.collate/exchange``, a loop's
count is its most frequent op instance's, the two capacities' loops add
up, chips are averaged, and a program with no such scope reads None,
never 0.
"""
import json
import os

import pytest

from perfbench import mesh_parts_reduce as parts, run, trace_reduce
from perfbench.layer_metrics import row_exchange_tiles_per_step as tiles

FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
X = ('glt.collate', 'exchange')
LOOP = ('cond', 'branch_1_fun', 'while', 'body')


def _run_of(trace, steps):
  device, host = trace_reduce.load(os.path.join(FIX, trace))
  return dict(scan=dict(device=device, host=host, steps=steps))


@pytest.mark.parametrize('path, want', [
    (X + LOOP + ('lookup', 'tile', 'gather'), ('branch_1_fun',)),
    (X + LOOP + ('rows', 'tile', 'dynamic_update_slice'), ('branch_1_fun',)),
    (X + ('cond', 'branch_0_fun', 'while', 'body', 'rows', 'tile',
          'jit(_where)', 'select_n'), ('branch_0_fun',)),
    # no cond (full-width posture, P = 1): one loop, no branch
    (X + ('while', 'body', 'lookup', 'tile', 'dynamic_slice'), ()),
    # the halvings' own loop, nested inside the body: once a round
    (X + LOOP + ('lookup', 'tile', 'while', 'body', 'closed_call', 'gather'),
     None),
    # the parts around the loop, and the one-piece lookup
    (X + ('cond', 'branch_1_fun', 'lookup', 'gather'), None),
    (X + ('cond', 'branch_1_fun', 'wire', 'all_to_all'), None),
    # a tile of the sampler's draw is the draw's counter's, not this one's
    (('glt.sample', 'hop2', 'draw', 'while', 'body', 'tile', 'gather'), None),
    (('glt.collate', 'cache', 'lookup', 'tile', 'gather'), None),
    ((), None)])
def test_an_op_is_counted_where_it_sits_directly_in_a_tile_body(path, want):
  assert tiles.tile_loop(path) == want
  if want is not None:                 # and its time stays its part's
    assert parts.part_of(path) == (parts.EXCHANGE, path[path.index(
        'tile') - 1])


def _op(chip, name, *path):
  return dict(lane=trace_reduce.OP_LANE, chip=f'/device:TPU:{chip}',
              name=name, ts=0.0, dur=1.0, args={'tf_op': '/'.join(path)})


def test_loops_add_up_instances_do_not_and_chips_are_averaged(capsys):
  body = X + LOOP
  fallback = X + ('cond', 'branch_0_fun', 'while', 'body')
  device = []
  for chip, n in ((0, 6), (1, 7)):
    device += [_op(chip, 'fusion.1', *body, 'rows', 'tile', 'gather')] * n
    # a short op of the same body, some executions cut from the trace
    device += [_op(chip, 'fusion.2', *body, 'lookup', 'tile', 'add')] * (n - 2)
    # the nested halvings: four rounds a tile, never counted
    device += [_op(chip, 'fusion.3', *body, 'lookup', 'tile', 'while',
                   'body', 'gather')] * (4 * n)
  # chip 1 fell back to the full width in one step: its own loop, 3 tiles
  device += [_op(1, 'fusion.9', *fallback, 'rows', 'tile', 'gather')] * 3
  device.append(dict(_op(0, 'jit_body', *body, 'rows', 'tile', 'gather'),
                     lane=trace_reduce.PROGRAM_LANE))
  run_ = dict(scan=dict(device=device, host=[], steps=2))
  assert tiles.tile_runs(device) == {'/device:TPU:0': 6, '/device:TPU:1': 10}
  assert tiles.read(run_) == pytest.approx((6 / 2 + 10 / 2) / 2)
  assert tiles.read(run_) == pytest.approx(4.0)       # kept in the run
  lines = [l for l in capsys.readouterr().out.splitlines()
           if 'row_exchange_tiles' in l]
  assert len(lines) == 1                              # said once
  said = json.loads(lines[0][len('perfbench: '):])['row_exchange_tiles']
  assert said['tiles_per_step_by_chip'] == {'/device:TPU:0': 3.0,
                                            '/device:TPU:1': 5.0}


def test_the_reader_on_a_recorded_trace_of_a_program_that_tiles(capsys):
  """The first scanned step of ``sage-papers.mesh-exact`` on each chip of
  a v5e 2x2 (hand-cut from PR 40's traced run of the change)."""
  with open(os.path.join(FIX, 'trace_v5e_mesh_tiles_cut.expected.json')) as f:
    want = json.load(f)
  run_ = _run_of('trace_v5e_mesh_tiles_cut.json', want['steps'])
  assert {e['chip'] for e in run_['scan']['device']} == {
      f'/device:TPU:{i}' for i in range(4)}
  got = tiles.read(run_)
  assert got == pytest.approx(want['metrics']['row_exchange_tiles_per_step'],
                              rel=1e-9)
  per_chip = tiles.tile_runs(run_['scan']['device'])
  assert per_chip == want['tile_runs_by_chip']
  # a valid prefix of ~ 8.5 % of 124,096 columns in tiles of 2,048
  assert all(4 <= n <= 8 for n in per_chip.values())
  # the parts still read the work the tiles do, under lookup and rows
  r = parts.parts(run_)
  for part in ('lookup', 'rows'):
    assert parts.ms(run_, parts.EXCHANGE, (part,)) == pytest.approx(
        want['parts_ms'][part], rel=1e-9)
    assert r[parts.EXCHANGE][part]['/device:TPU:0'] > 0
  assert 'row_exchange_tiles' in capsys.readouterr().out


@pytest.mark.parametrize('trace, steps', [
    ('trace_v5e_mesh_parts_cut.json', 1),  # four chips, PR 39: one piece
    ('trace_v5e_mesh_cut.json', 1),        # four chips, PR 35: no parts
    ('trace_v5e_tiled_cut.json', 2),       # one chip: the DRAW's tiles
    ('trace_v5e_cut.json', 2)])            # one chip, no mesh chunk
def test_the_reader_finds_nothing_where_no_block_is_tiled(trace, steps,
                                                          capsys):
  run_ = _run_of(trace, steps)
  assert tiles.tile_runs(run_['scan']['device']) == {}
  assert tiles.read(run_) is None
  assert 'row_exchange_tiles' not in capsys.readouterr().out


def test_the_entry_a_benchmark_pr_adds_is_the_readers():
  """``BENCHMARK.json`` cannot list the reader in this PR:
  ``test_mesh_parts.py`` holds PR 39's nine entries to be the tail of
  ``per_layer``, a file of the benchmark that only a ``benchmark`` PR may
  edit, and an entry put before them reads as a change to what was there
  (PERF.md section 7). Where the entry is listed, it is this one."""
  entry = dict(name='row_exchange_tiles_per_step', unit=tiles.UNIT,
               better='lower', source='device_trace', layer=tiles.LAYER,
               moves=tiles.MOVES, workloads=['sage-papers.mesh-exact'])
  assert (tiles.LAYER, tiles.UNIT, tiles.MOVES) == ('collate', 'count',
                                                    'seeds_per_s')
  with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  assert entry['workloads'][0] in [w['name'] for w in bench['workloads']]
  assert entry['moves'] in [m['name'] for m in bench['end_to_end']]
  assert entry['layer'] in {m['layer'] for m in bench['per_layer']}
  assert [m for m in bench['per_layer']
          if m['name'] == entry['name']] in ([], [entry])
