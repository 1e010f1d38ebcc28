"""The readers of the mesh step's opened scopes (PR 39):
``perfbench/mesh_parts_reduce.py`` and the nine entries that read it, on a
hand-cut recorded 4-chip v5e trace whose program names the parts, and on
the recorded traces whose programs do not.

Like the other files here these test the yardstick: the parts and what was
rooted outside them add up to the whole scope, the whole is what
``mesh_reduce`` reads one level up, a path is read through control flow and
``jit(...)`` components, and a program that names no part reads None,
never 0.
"""
import importlib
import json
import os

import pytest

from perfbench import mesh_parts_reduce as parts, mesh_reduce, run, trace_reduce

FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
READERS = ['row_exchange_ms', 'row_exchange_unsplit_ms',
           'row_exchange_dedup_ms', 'row_exchange_buckets_ms',
           'row_exchange_owner_ms', 'cache_lookup_ms', 'local_draw_ms',
           'local_draw_rows_ms', 'row_exchange_fill_share']
CELL = 'sage-papers.mesh-exact'


class _Cell:
  """What the readers ask of a cell, for a recorded trace."""
  parts = 4

  def exchange_bytes(self, counters, steps):
    return {}


def _run_of(trace, steps, window):
  device, host = trace_reduce.load(os.path.join(FIX, trace))
  busy_s, window_s, gaps = trace_reduce.busy(device,
                                             trace_reduce.window_of(host))
  return dict(cell=_Cell(), traffic={}, counts={}, peaks={}, window=window,
              scan=dict(device=device, host=host, steps=steps, busy_s=busy_s,
                        window_s=window_s, gaps=gaps))


def _read(run_):
  return {n: importlib.import_module(f'perfbench.layer_metrics.{n}').read(run_)
          for n in READERS}


@pytest.fixture
def slots_gauge():
  """The gauge a program of PR 39 sets when it builds its lookup body."""
  import graphlearn_tpu as glt

  def set_(value):
    glt.metrics.reset('dist_feature.exchange_slots')
    if value is not None:
      glt.metrics.set_gauge('dist_feature.exchange_slots', value)
  yield set_
  glt.metrics.reset('dist_feature.exchange_slots')


def test_the_entries_are_the_cells_and_each_has_its_file():
  """Every ``per_layer`` entry of ``BENCHMARK.json`` has its reader file
  with the entry's ``LAYER`` / ``UNIT`` / ``MOVES``; the nine of this PR
  are appended after the accepted ones, list the mesh cell alone, and none
  is named like ``mesh_reduce``'s eight."""
  with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  for m in bench['per_layer']:
    path = os.path.join(run.ROOT, 'perfbench', 'layer_metrics',
                        m['name'] + '.py')
    assert os.path.isfile(path), m['name']
    mod = importlib.import_module(f'perfbench.layer_metrics.{m["name"]}')
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m['layer'], m['unit'],
                                                m['moves']), m['name']
    assert callable(mod.read)
  mine = bench['per_layer'][-len(READERS):]
  assert [m['name'] for m in mine] == READERS
  assert all(m['workloads'] == [CELL] and m['moves'] == 'seeds_per_s'
             for m in mine)
  assert not any(m['name'].startswith('mesh_') for m in mine)
  by = {m['name']: m for m in mine}
  assert by['row_exchange_fill_share']['better'] == 'higher'
  assert by['row_exchange_fill_share']['source'] == 'program_counter'
  assert all(m['better'] == 'lower' and m['source'] == 'device_trace'
             for n, m in by.items() if n != 'row_exchange_fill_share')


@pytest.mark.parametrize('path, want', [
    (('glt.collate', 'exchange', 'cond', 'branch_0_fun', 'pack',
      'jit(scatter)', 'scatter'), ('glt.collate/exchange', 'pack')),
    (('glt.collate', 'exchange', 'cond', 'branch_1_fun', 'wire',
      'all_to_all'), ('glt.collate/exchange', 'wire')),
    (('glt.collate', 'exchange', 'dedup', 'jit(masked_unique)', 'sort'),
     ('glt.collate/exchange', 'dedup')),
    (('glt.collate', 'exchange', 'eq'), ('glt.collate/exchange', 'unsplit')),
    (('glt.collate', 'exchange'), ('glt.collate/exchange', 'unsplit')),
    (('glt.collate', 'cache', 'lookup', 'while', 'body', 'gather'),
     ('glt.collate/cache', 'lookup')),
    (('glt.collate', 'cache', 'not'), ('glt.collate/cache', 'unsplit')),
    (('glt.collate', 'jit(_where)', 'select_n'), None),
    (('glt.sample', 'hop2', 'draw', 'jit(uniform_sample_local)', 'rows',
      'while', 'body', 'gather'), ('glt.sample/hop2/draw', 'rows')),
    (('glt.sample', 'cond', 'branch_1_fun', 'hop1', 'draw',
      'jit(uniform_sample_local)', 'gather'),
     ('glt.sample/hop1/draw', 'unsplit')),
    (('glt.sample', 'hop1', 'exchange', 'all_to_all'), None),
    (('glt.train', 'fwd_bwd', 'transpose(jvp(rows))', 'dot'), None),
    ((), None)])
def test_a_path_is_read_through_control_flow_and_jit_components(path, want):
  assert parts.part_of(path) == want


def test_the_nine_readers_on_a_recorded_trace_that_names_the_parts(
    capsys, slots_gauge):
  """The first scanned step of ``sage-papers.mesh-exact`` on each chip of
  a v5e 2x2 (hand-cut, PR 39)."""
  with open(os.path.join(FIX, 'trace_v5e_mesh_parts_cut.expected.json')) as f:
    want = json.load(f)
  slots_gauge(want['exchange_slots'])
  run_ = _run_of('trace_v5e_mesh_parts_cut.json', want['steps'],
                 dict(want['window'], wall_s=1.0))
  assert {e['chip'] for e in run_['scan']['device']} == {
      f'/device:TPU:{i}' for i in range(4)}
  got = _read(run_)
  for name in READERS:
    assert got[name] == pytest.approx(want['metrics'][name], rel=1e-9), name
    assert got[name] is not None
  r = parts.parts(run_)
  for group, by_part in want['parts_chip0'].items():
    for part, ms in by_part.items():
      assert r[group][part]['/device:TPU:0'] == pytest.approx(
          ms, rel=1e-9, abs=1e-12), (group, part)
  # every registered part ran, under the fractional buckets' branch
  assert set(parts.EXCHANGE_PARTS) <= set(r[parts.EXCHANGE])
  assert set(parts.CACHE_PARTS) <= set(r[parts.CACHE])
  # the parts and what was rooted outside them add up to the whole, a chip
  # at a time and on the mean
  for group, by_part in r.items():
    for chip, whole in by_part[parts.WHOLE].items():
      assert sum(v[chip] for p, v in by_part.items()
                 if p != parts.WHOLE) == pytest.approx(whole, rel=1e-9)
  eight = sum(parts.ms(run_, parts.EXCHANGE, (p,))
              for p in parts.EXCHANGE_PARTS)
  assert eight + got['row_exchange_unsplit_ms'] == pytest.approx(
      got['row_exchange_ms'], rel=1e-9)
  assert (got['row_exchange_dedup_ms'] + got['row_exchange_buckets_ms'] +
          got['row_exchange_owner_ms'] +
          parts.ms(run_, parts.EXCHANGE, ('wire',))) == pytest.approx(
              eight, rel=1e-9)
  # the whole is what mesh_reduce reads one level up
  subs = mesh_reduce.chips(run_)['sub_scopes']
  assert got['row_exchange_ms'] == pytest.approx(subs[parts.EXCHANGE],
                                                 rel=1e-9)
  assert got['local_draw_ms'] == pytest.approx(
      sum(v for k, v in subs.items() if k.endswith('/draw')), rel=1e-9)
  assert parts.ms(run_, parts.CACHE, (parts.WHOLE,)) == pytest.approx(
      subs[parts.CACHE], rel=1e-9)
  # the wire holds the two all_to_alls, which XLA names after the jax
  # primitive (`all_to_all.101`); the one collective `mesh_reduce` matches
  # by name under the scope is the overflow count's all-reduce, in `route`
  from perfbench import scope_reduce
  by_part = {}
  for e in run_['scan']['device']:
    found = parts.part_of(scope_reduce.scope_path(e))
    if found and found[0] == parts.EXCHANGE:
      by_part.setdefault(found[1], set()).add(e['name'].split('.')[0])
  assert 'all_to_all' in by_part['wire']
  assert 'all-reduce' in by_part['route']
  assert not any(mesh_reduce.is_collective(dict(name=n))
                 for n in by_part['wire'])
  assert 0 <= got['local_draw_rows_ms'] <= got['local_draw_ms']
  assert 5 < got['row_exchange_fill_share'] < 15
  assert got['row_exchange_fill_share'] == pytest.approx(
      100.0 * want['window']['counters']['dist_feature.unique_misses'] /
      (want['window']['steps'] * 4 * want['exchange_slots']), rel=1e-12)
  lines = [json.loads(l[len('perfbench: '):])
           for l in capsys.readouterr().out.splitlines()
           if l.startswith('perfbench: ')]
  mine = [l for l in lines if 'mesh_parts_reduce' in l]
  assert len(mine) == 1                          # reduced and printed once
  line = mine[0]['mesh_parts_reduce']
  assert line[parts.EXCHANGE][parts.WHOLE]['mean'] == pytest.approx(
      got['row_exchange_ms'], rel=1e-9)
  assert all(v['max'] >= v['mean'] for by in line.values()
             for v in by.values())
  assert {'glt.sample/hop0/draw', 'glt.sample/hop1/draw',
          'glt.sample/hop2/draw'} <= set(line)


@pytest.mark.parametrize('trace, steps', [
    ('trace_v5e_mesh_cut.json', 1),       # four chips, PR 35: no parts
    ('trace_v5e_cut.json', 2)])           # one chip, no mesh chunk
def test_every_reader_finds_nothing_where_no_part_is_named(trace, steps,
                                                           slots_gauge,
                                                           capsys):
  """A program from before PR 39 names the scopes and none of their parts
  and sets no gauge: every new reader returns None — never 0 — the whole
  scopes' too, and no line is printed."""
  slots_gauge(None)
  run_ = _run_of(trace, steps, dict(
      steps=48, wall_s=1.0,
      counters={'dist_feature.unique_misses': 8076048}))
  assert _read(run_) == dict.fromkeys(READERS)
  assert parts.parts(run_) is None
  assert 'mesh_parts_reduce' not in capsys.readouterr().out


def test_the_fill_share_needs_the_gauge_the_counter_and_the_steps(
    slots_gauge):
  run_ = dict(cell=_Cell(), window=dict(
      steps=10, counters={'dist_feature.unique_misses': 400}))
  slots_gauge(25)
  assert parts.fill_share(run_) == pytest.approx(100.0 * 400 / (10 * 4 * 25))
  run_['window']['counters'] = {}
  assert parts.fill_share(run_) is None
  run_['window'] = dict(steps=0, counters={'dist_feature.unique_misses': 4})
  assert parts.fill_share(run_) is None
  slots_gauge(None)
  run_['window']['steps'] = 10
  assert parts.fill_share(run_) is None
