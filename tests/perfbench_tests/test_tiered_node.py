"""Family ``tiered_node`` (PR 41): the cell whose feature table does not fit
the chip — its ``Cell``, its plain reference, its executor and its eleven
readers — added as files, run through ``run.main`` and ``control.main`` as
they stand.

Like the other files here these test the yardstick: the whole command at a
toy shape with ``correct`` true and false under planted faults (half of the
seeds left out; a state left unchanged; a slab row zeroed; a planned row
dropped), the family's two new exact numbers at 0 and tripped by a fault
planted in what the check reads, the reference's loss by hand, the gather's
bytes by hand, and the readers on a hand-cut recorded v5e trace of the
cell's own plan and chunk programs.
"""
import copy
import importlib
import json
import os

import numpy as np
import pytest

from perfbench import (control, datagen_mesh_node, flops, flops_tiered_node,
                       reference_tiered_node, run, scope_reduce, tier_reduce,
                       trace_reduce)
from perfbench.executors import tiered_scan
from perfbench.families import tiered_node
from test_perfbench import TINY, rehearse

TIERED = dict(TINY, bench_file='perfbench/fixtures/BENCHMARK.tiered.json')
CELL = 'tiny-papers-tiered.tiny-tiered-scan'
REAL = 'sage-papers-tiered.tiered-scan-exact'
FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
READERS = ['tier_sample_ms', 'tier_collate_ms', 'tier_train_ms',
           'tier_unscoped_ms', 'tier_plan_ms', 'tier_gather_ms',
           'tier_gather_roofline', 'tier_host_gap_ms', 'tier_hit_share',
           'tier_slab_fill_share', 'tier_prefetch_miss_rows']
LAYERS = READERS[:4]


@pytest.fixture(scope='module')
def one_cell():
  """The toy dataset, built once: every run of this file sees the same
  graph, rows, storage order and caps, as every seed of a cell does."""
  _, _, cfg, traffic, _ = run.load_cell(CELL, TIERED['bench_file'])
  return tiered_node.Cell(cfg, traffic, lambda k, v: None)


@pytest.fixture
def shared_cell(one_cell, monkeypatch):
  monkeypatch.setattr(tiered_node, 'Cell', lambda cfg, traffic, log: one_cell)
  return one_cell


@pytest.fixture(scope='module')
def replayed(one_cell):
  """One first call of the toy cell and its replayed chunk."""
  ex = tiered_scan.Executor(one_cell, one_cell.traffic, 4321)
  first = ex.first_call()
  batches = ex.replay(first['steps'], 2)
  params0 = ex.params0
  ex.free()
  return first, batches, params0


def test_the_reference_imports_nothing_of_the_program():
  with open(reference_tiered_node.__file__) as f:
    source = f.read()
  assert 'graphlearn_tpu' not in source
  assert 'optax' not in source


# ------------------------------------------ the family through the drivers


def test_the_tiered_family_runs_through_the_same_driver(shared_cell, capsys):
  said = {}
  out = rehearse(capsys, CELL, trace=1, fixtures=TIERED, said=said)
  assert list(out)[-1] == 'compared' and out['correct'] is True
  assert out['attempted'] > 0
  exact = {k: v for k, v in out['compared'].items()
           if k not in control.MEASURED}
  assert set(exact) == set(tiered_node.EXACT)
  assert all(v == {'value': 0, 'limit': 0} for v in exact.values())
  assert set(control.MEASURED) <= set(out['compared'])
  cell = shared_cell
  store = cell.dataset.node_features
  assert (said['hot_rows'], said['warm_rows']) == (600, 3401) == \
      (store.hot_rows, store.warm_rows)
  assert store._hot_np is None and store.disk_rows == 0   # the no-copy door
  assert cell.dataset.graph.edge_ids is None              # no dead bytes
  # the program's counters over the window
  win = said['window']
  assert win['seeds'] == win['steps'] * cell.batch
  tier = win['tier']
  assert 0 < tier['hot_hits'] < tier['lookups']
  assert 0 < tier['planned_rows'] <= tier['slab_cap_rows']
  assert tier['prefetch_miss'] == 0 and tier['staged_bytes'] > 0
  counts = said['valid_counts']
  assert counts['buffer_rows'] == cell.node_offsets[-1]
  assert tier['lookups'] <= win['steps'] * counts['buffer_rows']
  assert cell.step_flops(counts['nodes'], counts['edges']) > 0
  assert cell.gather_bytes(counts['nodes']) == \
      sum(counts['nodes']) * (2 * 8 * 4 + 8)


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


def _half_seeds(monkeypatch):
  """The second half of a step's seeds left out of the loss, the mean
  over the rest."""
  from graphlearn_tpu.models import train as train_lib
  real = train_lib.make_train_step

  def broken(model, tx, num_classes):
    step, ev = real(model, tx, num_classes)
    return (lambda state, b: step(state, dict(
        b, num_seed_nodes=b['num_seed_nodes'] // 2))), ev

  monkeypatch.setattr(train_lib, 'make_train_step', broken)


def _zeroed_slab_row(monkeypatch):
  """A staging buffer with one row lost: the first row of every slab is
  zeros (what a half-written ring slot would hand over)."""
  from graphlearn_tpu.storage import staging
  real = staging.ChunkStager._gather

  def broken(self, rows_abs):
    ids, slab = real(self, rows_abs)
    slab[0] = 0
    return ids, slab

  monkeypatch.setattr(staging.ChunkStager, '_gather', broken)


def _dropped_planned_row(monkeypatch):
  """A plan that loses a row: every chunk's miss set without its first
  row, so the slab does not hold it and the gather reads zeros."""
  from graphlearn_tpu.storage import planner
  real = planner.chunk_misses
  monkeypatch.setattr(planner, 'chunk_misses',
                      lambda *a, **kw: real(*a, **kw)[1:])


@pytest.mark.parametrize('fault,caught_by', [
    (_unchanged_state, 'dparam_gap'), (_half_seeds, 'loss_gap_step1'),
    (_zeroed_slab_row, 'bad_rows'), (_dropped_planned_row, 'unplanned_rows')])
def test_a_broken_tiered_path_comes_out_not_correct(
    fault, caught_by, shared_cell, monkeypatch, capsys):
  fault(monkeypatch)
  out = rehearse(capsys, CELL, seed=77, fixtures=TIERED)
  row = out['compared'][caught_by]
  assert out['correct'] is False and row['value'] > row['limit'], \
      out['compared']
  if caught_by == 'bad_rows':
    # the plan was whole: the slab, not the plan, was at fault
    assert out['compared']['unplanned_rows']['value'] == 0
  if caught_by == 'unplanned_rows':
    # the gather read zeros for the rows the plan lost
    assert out['compared']['bad_rows']['value'] > 0
  assert out['compared']['bad_edges']['value'] == 0


def test_control_reads_both_ends_through_the_tiered_follower(shared_cell,
                                                             capsys):
  """``control.py`` as it stands: program readings under the limits, the
  reference in bfloat16 and both planted faults over them."""
  readings = control.main(
      ['--workload', CELL, '--seeds', '2', '--control-seeds', '1'], **TIERED)
  capsys.readouterr()
  _, _, _, _, limits = run.load_cell(CELL, TIERED['bench_file'])
  by_kind = {}
  for r in readings:
    by_kind.setdefault(r['kind'], []).append(r)
  for r in by_kind['program']:
    assert all(r[k] <= limits[k] for k in limits), r
  assert by_kind['control_ref_bf16'][0]['loss_gap_step1'] > \
      limits['loss_gap_step1']
  assert by_kind['fault_half_batch'][0]['loss_gap_step1'] > \
      limits['loss_gap_step1']
  assert by_kind['fault_state_unchanged'][0]['dparam_gap'] == 1.0


# ----------------------------------------------- the family's exact numbers


def test_the_new_numbers_are_zero_and_trip_on_their_faults(one_cell,
                                                           replayed):
  _, batches, _ = replayed
  clean = one_cell.exact_numbers(batches, 2)
  assert set(clean) == set(tiered_node.EXACT)
  assert all(v == 0 for v in clean.values()), clean
  # a slab that lacks a row a batch needs
  broken = copy.deepcopy(batches)
  valid = int(np.asarray(batches[2]['num_sampled_nodes']).sum())
  rows = one_cell.id2index[np.asarray(batches[2]['node'])[:valid]]
  lost = rows[rows >= one_cell.hot_rows][0]      # a row the third step reads
  broken[0]['slab_ids'] = np.setdiff1d(broken[0]['slab_ids'], [lost])
  got = one_cell.exact_numbers(broken, 2)
  assert got['unplanned_rows'] > 0 and got['bad_rows'] == 0
  # a batch row that is another id's row
  broken = copy.deepcopy(batches)
  broken[1]['x'][3] = broken[1]['x'][4]
  got = one_cell.exact_numbers(broken, 2)
  assert got['bad_rows'] == 1 and got['unplanned_rows'] == 0


def test_bad_hot_rows_reads_the_devices_own_prefix(one_cell, replayed,
                                                   monkeypatch):
  """The hot prefix is checked where it lives: a prefix filled with
  another order's rows (two rows swapped on the device) trips
  ``bad_hot_rows`` and nothing else."""
  import jax
  _, batches, _ = replayed
  store = one_cell.dataset.node_features
  hot = np.asarray(store._hot_dev).copy()
  hot[[0, 1]] = hot[[1, 0]]
  monkeypatch.setattr(store, '_hot_dev', jax.device_put(hot))
  got = one_cell.exact_numbers(batches, 2)
  assert got['bad_hot_rows'] == 2
  assert all(v == 0 for k, v in got.items() if k != 'bad_hot_rows')


def test_the_storage_order_is_the_hot_first_one(one_cell):
  """Hot prefix = the 600 highest in-degrees (ties by id), as the full
  stable sort would put them; every row the store serves is the
  generator's row for the id the order puts there."""
  cell = one_cell
  n, h = cell.num_nodes, cell.hot_rows
  indeg = np.bincount(cell.indices[:int(cell.indptr[-1])], minlength=n)
  full = np.argsort(-indeg, kind='stable')
  np.testing.assert_array_equal(cell.index2id[:h], full[:h])
  assert (np.diff(cell.index2id[h:]) > 0).all()
  np.testing.assert_array_equal(cell.id2index[cell.index2id], np.arange(n))
  ids = np.array([0, 1, 17, 4000, int(full[0]), int(full[h]), int(full[-1])])
  store = cell.dataset.node_features
  np.testing.assert_array_equal(store.cpu_get(ids).view(np.uint32),
                                cell.rows(ids).view(np.uint32))
  assert int(cell.indptr[-1]) == 40003        # the one-partition graph


def test_the_replay_is_the_chunks_own_batches(one_cell, replayed):
  """The reference over the replayed batches reproduces the first call's
  losses (a chunk that had trained on other rows could not), and the
  validated batches carry rows gathered through the trainer's own slab."""
  first, batches, params0 = replayed
  losses, *_ = one_cell.follower(params0, batches)()
  np.testing.assert_allclose(losses, first['losses'][:len(batches)],
                             rtol=1e-5)
  assert len(batches) == first['steps'] == 4
  assert 'x' in batches[0] and 'x' in batches[1] and 'x' not in batches[2]
  slab_ids = batches[0]['slab_ids']
  live = slab_ids[slab_ids != np.iinfo(np.int32).max]
  assert live.size and (np.diff(live) > 0).all()
  assert live.min() >= one_cell.hot_rows


# ------------------------------------------------- the reference, by hand


def test_the_reference_loss_by_hand():
  """Two live nodes and a pad, one edge, identity-like weights: rows from
  ids, the forward, the cross-entropy over the one seed row worked out by
  hand; a slot that is not live reads zeros whatever its id."""
  import jax.numpy as jnp
  model = dict(kind='sage', in_dim=2, hidden=2, out_dim=2, layers=1, heads=1)
  eye = np.eye(2, dtype=np.float32)
  params = {'params': {'conv0': {
      'lin_self': {'kernel': eye, 'bias': np.zeros(2, np.float32)},
      'lin_nbr': {'kernel': eye}}}}
  table = jnp.asarray([[1., 2.], [3., -1.], [9., 9.]], jnp.float32)
  batch = dict(ids=np.array([0, 1, 2], np.int32),
               live=np.array([True, True, False]),
               y=np.array([1], np.int32), src=np.array([1, 2], np.int32),
               tgt=np.array([0, 0], np.int32), emask=np.array([True, False]))
  # h0 = x0 + mean(x1) = [4, 1]; loss = -log softmax([4, 1])[1]
  want = -np.log(np.exp(1.0) / (np.exp(4.0) + np.exp(1.0)))
  zeros = {'params': {'conv0': {
      'lin_self': {'kernel': 0 * eye, 'bias': np.zeros(2, np.float32)},
      'lin_nbr': {'kernel': 0 * eye}}}}
  step = reference_tiered_node.make_step(model, 0.0, 1, lambda i: table[i])
  *_, loss, grads = step(params, zeros, zeros, 0, batch)
  assert float(loss) == pytest.approx(want, rel=1e-6)
  assert float(jnp.abs(grads['params']['conv0']['lin_nbr']['kernel'])
               .sum()) > 0
  # the pad slot's row is never read as 9s: make it a live neighbour and
  # the loss moves
  *_, moved, _ = step(params, zeros, zeros, 0, dict(
      batch, live=np.array([True, True, True]),
      emask=np.array([True, True])))
  assert float(moved) != pytest.approx(want, rel=1e-3)


def test_the_gathers_bytes_by_hand():
  assert flops_tiered_node.gather_bytes(1000, 128, 4) == \
      1000 * (2 * 512 + 8)
  assert flops_tiered_node.gather_bytes(0, 128, 4) == 0
  model = dict(kind='sage', in_dim=128, hidden=256, out_dim=172, layers=3,
               heads=1)
  nodes, edges = [1024, 15000, 110000, 240000], [15360, 150000, 550000]
  assert flops_tiered_node.step_flops(model, nodes, edges) == \
      flops.step_flops(model, nodes, edges)
  with pytest.raises(ValueError, match='unknown model kind'):
    flops_tiered_node.step_flops(dict(model, kind='gat'), nodes, edges)


# --------------------------------------- the readers on a recorded v5e trace


def _run_from(trace_file, steps, window=None, counts=None, cell=None):
  device, host = trace_reduce.load(os.path.join(FIX, trace_file))
  busy_s, window_s, _ = trace_reduce.busy(device)
  slice_ = dict(device=device, host=host, steps=steps, busy_s=busy_s,
                window_s=window_s)
  return dict(cell=cell, traffic={}, peaks={'hbm_bytes_per_s': 819e9},
              counts=counts or {},
              window=window or dict(steps=0, wall_s=0.0), scan=slice_)


def _read(name, run_):
  return importlib.import_module(
      f'perfbench.layer_metrics.{name}').read(run_)


class _Bytes:
  gather_bytes = staticmethod(
      lambda nodes: flops_tiered_node.gather_bytes(sum(nodes), 128, 4))


def test_the_tier_readers_on_a_recorded_v5e_tiered_call(capsys):
  with open(os.path.join(FIX, 'trace_v5e_tier_cut.expected.json')) as f:
    want = json.load(f)
  run_ = _run_from('trace_v5e_tier_cut.json', want['steps'],
                   counts=dict(nodes=want['valid_nodes']), cell=_Bytes)
  trace_readers = READERS[:8]
  got = {name: _read(name, run_) for name in trace_readers}
  for name, v in want['metrics'].items():
    assert got[name] == pytest.approx(v, rel=1e-9), name
  assert set(want['metrics']) == set(trace_readers)
  line = [json.loads(l[len('perfbench: '):])
          for l in capsys.readouterr().out.splitlines()
          if l.startswith('perfbench: {"tier_reduce"')]
  assert len(line) == 1                       # reduced once, said once
  split = line[0]['tier_reduce']
  assert set(split) == {'glt.plan', 'glt.collate/tier/lookup',
                        'glt.collate/tier/hot', 'glt.collate/tier/rows'}
  for k, v in want['tier_reduce'].items():
    assert split[k] == pytest.approx(v, rel=1e-9), k
  # the gather is its three parts and a part of the collate layer; the
  # plan runs in another program, so no chunk layer holds it; the four
  # layers add up to the chunk program's busy time
  assert got['tier_gather_ms'] == pytest.approx(
      sum(v for k, v in split.items() if k != 'glt.plan'))
  assert got['tier_gather_ms'] < got['tier_collate_ms']
  assert got['tier_plan_ms'] == split['glt.plan'] > 0
  scopes, _ = scope_reduce.by_scope(run_['scan']['device'],
                                    scope_reduce.CHUNK_STEM)
  assert sum(got[n] for n in LAYERS) == pytest.approx(
      1e3 * sum(scopes.values()) / want['steps'], rel=1e-9)
  assert not any(k.startswith('glt.plan') for k in scopes)
  # the share of the roofline: bytes over the peak over the scope's time
  assert got['tier_gather_roofline'] == pytest.approx(
      100.0 * _Bytes.gather_bytes(want['valid_nodes']) / 819e9 /
      (got['tier_gather_ms'] / 1e3))
  assert 0 < got['tier_gather_roofline'] < 100
  # the idle gaps inside the call carry the tiered trainer's span names;
  # the wait for the call's first slab is named by the innermost span
  # open in it — the worker's ``storage.stage``, inside ``epoch.stage_wait``
  gaps = scope_reduce.host_gaps(run_['scan']['device'], run_['scan']['host'])
  assert set(gaps) <= {'epoch.run', 'epoch.stage', 'epoch.plan',
                       'epoch.stage_wait', 'storage.stage', 'epoch.upload',
                       'epoch.chunk', 'epoch.publish', 'epoch.concat'}
  assert max(gaps, key=gaps.get) == 'storage.stage'
  assert got['tier_host_gap_ms'] == pytest.approx(
      1e3 * sum(gaps.values()) / want['steps'])


def test_the_tier_readers_find_nothing_in_an_all_hbm_program():
  """Over an all-HBM cell's chunk (the recorded scan trace) the tier split,
  the plan's and the gather's readers return None — never 0; the layer
  readers read the layers whatever program it is."""
  run_ = _run_from('trace_v5e_scan_cut.json', 2,
                   counts=dict(nodes=[1024, 15000]), cell=_Bytes)
  assert tier_reduce.split(run_) is None
  for name in ('tier_plan_ms', 'tier_gather_ms', 'tier_gather_roofline',
               'tier_hit_share', 'tier_slab_fill_share',
               'tier_prefetch_miss_rows'):
    assert _read(name, run_) is None, name
  assert _read('tier_sample_ms', run_) > 0


WINDOW = dict(calls=3, tier=dict(lookups=1000, hot_hits=420,
                                 planned_rows=300, slab_cap_rows=512,
                                 prefetch_miss=6))


@pytest.mark.parametrize('name,window,want', [
    ('tier_hit_share', WINDOW, 42.0),
    ('tier_hit_share', dict(tier=dict(lookups=0, hot_hits=0)), None),
    ('tier_hit_share', dict(), None),
    ('tier_slab_fill_share', WINDOW, 100.0 * 300 / 512),
    ('tier_slab_fill_share', dict(tier=dict(planned_rows=0)), None),
    ('tier_prefetch_miss_rows', WINDOW, 2.0),
    ('tier_prefetch_miss_rows',
     dict(calls=2, tier=dict(prefetch_miss=0)), 0.0),
    ('tier_prefetch_miss_rows', dict(calls=2), None)])
def test_the_share_readers_read_the_windows_counters(name, window, want):
  got = _read(name, dict(window=window))
  assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize('path,want', [
    (('glt.plan', 'while', 'body', 'glt.sample', 'hop1', 'draw', 'gather'),
     'glt.plan'),
    (('glt.plan', 'reduce_sum'), 'glt.plan'),
    (('glt.collate', 'tier', 'lookup', 'while', 'body', 'gather'),
     'glt.collate/tier/lookup'),
    (('glt.collate', 'tier', 'hot', 'gather'), 'glt.collate/tier/hot'),
    (('glt.collate', 'tier', 'rows', 'select_n'), 'glt.collate/tier/rows'),
    (('glt.collate', 'tier', 'clamp'), 'glt.collate/tier/other'),
    (('glt.collate', 'tier'), 'glt.collate/tier/other'),
    (('glt.collate', 'jit(collate_batch)', 'gather'), None),
    (('glt.collate', 'cache', 'lookup', 'gather'), None),
    (('glt.sample', 'hop0', 'draw', 'gather'), None),
    (('glt.train', 'update', 'add'), None), ((), None)])
def test_tier_scope_names_the_tiered_scopes_and_nothing_else(path, want):
  assert tier_reduce.tier_scope(path) == want


# ------------------------------------------------------ the benchmark's entries


def _bench():
  with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
    return json.load(f)


def test_the_entries_a_benchmark_pr_adds_are_the_readers():
  """``BENCHMARK.json`` cannot list the eleven readers in this PR:
  ``test_mesh_parts.py`` holds PR 39's nine entries to be the tail of
  ``per_layer``, a file of the benchmark that only a ``benchmark`` PR may
  edit, and an entry put before them reads as a change to what was there
  (PERF.md section 7, as PR 40 found for its one). The entries are the toy
  benchmark's, with the real cell for the toy one; each is its reader's
  ``LAYER`` / ``UNIT`` / ``MOVES``; where the benchmark lists one, wherever
  in the list, it is this one."""
  with open(os.path.join(run.ROOT, TIERED['bench_file'])) as f:
    toy = json.load(f)
  entries = [dict(m, workloads=[REAL]) for m in toy['per_layer']
             if m['name'].startswith('tier_')]
  assert [m['name'] for m in entries] == READERS
  assert all(m['workloads'] == [CELL] for m in toy['per_layer']
             if m['name'].startswith('tier_'))
  for m in entries:
    mod = importlib.import_module(f'perfbench.layer_metrics.{m["name"]}')
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m['layer'], m['unit'],
                                                'seeds_per_s'), m['name']
    assert set(m) == {'name', 'unit', 'better', 'source', 'layer', 'moves',
                      'workloads'}
  by = {m['name']: m for m in entries}
  for name in ('tier_hit_share', 'tier_slab_fill_share',
               'tier_gather_roofline'):
    assert by[name]['better'] == 'higher' and by[name]['unit'] == '%'
  assert all(m['better'] == 'lower' for n, m in by.items()
             if not (n.endswith('_share') or n.endswith('_roofline')))
  assert all(m['source'] == 'program_counter' for n, m in by.items()
             if n in READERS[8:])
  assert all(m['source'] == 'device_trace' for n, m in by.items()
             if n in READERS[:8])
  bench = _bench()
  assert REAL in [w['name'] for w in bench['workloads']]
  assert 'seeds_per_s' in [m['name'] for m in bench['end_to_end']]
  listed = [m for m in bench['per_layer'] if m['name'].startswith('tier_')]
  assert all(m == by[m['name']] for m in listed)
  # what the cell reports meanwhile: the accepted metrics that list no
  # cells, each of which moves the cell's end-to-end metric
  everywhere = [m for m in bench['per_layer'] if 'workloads' not in m]
  assert {m['name'] for m in everywhere} >= {
      'device_idle_share', 'dispatches_per_step', 'step_mfu', 'pad_share'}
  assert all(m['moves'] == 'seeds_per_s' for m in everywhere)


def test_the_configuration_and_its_cell_are_in_the_benchmark():
  """``BENCHMARK.json`` gained the configuration and its one one-chip
  cell; the configuration's file states the cut and a table larger than
  the chip; rows are the mesh generator's law."""
  bench = _bench()
  config = next(c for c in bench['configs']
                if c['name'] == 'sage-papers-tiered')
  assert config['reduced'] == ['dataset_scale']
  assert config['file'] == 'perfbench/configs/sage-papers-tiered.json'
  assert len(config['source']) <= 200 and len(config['why']) <= 200
  cell = next(w for w in bench['workloads'] if w['name'] == REAL)
  assert (cell['config'], cell['traffic'], cell['chips']) == (
      'sage-papers-tiered', 'tiered-scan-exact', 1)
  assert len(cell['why']) <= 200
  _, _, cfg, traffic, limits = run.load_cell(REAL, 'BENCHMARK.json')
  d, fs = cfg['dataset'], cfg['feature_store']
  with open(os.path.join(run.ROOT, 'perfbench', 'configs',
                         'sage-papers.json')) as f:
    mesh = json.load(f)
  assert {k: d[k] for k in mesh['dataset'] if k != 'scale'} == \
      {k: v for k, v in mesh['dataset'].items() if k != 'scale'}
  assert cfg['graph_seed'] == mesh['graph_seed']
  assert cfg['published'] == mesh['published']
  table = d['num_nodes'] * d['feat_dim'] * 4
  assert table == fs['table_bytes'] > 16 * 2 ** 30     # past the chip
  assert fs['hot_rows'] == round(fs['split_ratio'] * d['num_nodes']) == \
      5_552_998 and fs['disk_rows'] == 0
  assert cfg['steps_per_call'] == 3 * traffic['chunk_size'] == 48 == \
      traffic['trace_steps']
  assert traffic['reference_steps'] == traffic['chunk_size'] == 16
  assert set(tiered_node.EXACT) <= set(limits)
  assert all(limits[k] == 0 for k in tiered_node.EXACT)
  assert datagen_mesh_node.rows_of is tiered_node.rows_of


def test_the_fixture_benchmark_names_only_files_that_exist():
  """The toy cell's fixture file holds what ``test_perfbench`` asks of
  every benchmark file (run as the other fixture benchmarks are)."""
  from test_perfbench import test_benchmark_json_names_only_files_that_exist
  test_benchmark_json_names_only_files_that_exist(TIERED['bench_file'])


def test_the_one_chip_generator_is_the_mesh_generator_at_one_partition():
  """``datagen_tiered_node.generate`` draws, bit for bit, the graph
  ``datagen_mesh_node.generate`` draws over a one-device mesh, and its
  host ``bincount`` is that generator's in-degree."""
  import jax
  from jax.sharding import Mesh
  from perfbench import datagen_tiered_node
  args = (4001, 40003, 5, 8, 0.58, 0.5, 1200, 7, 64)
  got = datagen_tiered_node.generate(*args)
  want = datagen_mesh_node.generate(
      Mesh(np.array(jax.devices()[:1]), ('g',)), *args)
  np.testing.assert_array_equal(got['indptr'],
                                np.asarray(want['graph']['indptr'])[0])
  np.testing.assert_array_equal(got['indices'],
                                np.asarray(want['graph']['indices'])[0])
  np.testing.assert_array_equal(got['in_degree'],
                                np.asarray(want['in_degree']))
  np.testing.assert_array_equal(got['train_idx'], want['train_idx'])
  np.testing.assert_array_equal(got['centres'], want['centres'])
  assert int(got['indptr'][-1]) == 40003 == int(got['in_degree'].sum())
