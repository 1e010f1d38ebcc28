"""Family ``homo_link`` (PR 37): the edge-seeded job's ``Cell``, its plain
reference, its executor and its six readers — added as files, run through
``run.main`` and ``control.main`` as they stand.

Like the other files here these test the yardstick: the whole command at a
toy shape with ``correct`` true and false under two planted faults (half of
the pairs left out; a state left unchanged), each of the family's five exact
numbers at 0 and tripped by a fault planted in a replayed batch, the
reference's loss by hand, the operation count by hand, and the link readers
on a hand-cut recorded v5e trace of the cell's own chunk program.
"""
import copy
import importlib
import json
import os

import numpy as np
import pytest

from perfbench import (control, flops, flops_homo_link, link_reduce,
                       reference_homo_link, run, scope_reduce, trace_reduce)
from perfbench.executors import link_scan
from perfbench.families import homo_link
from test_perfbench import TINY, rehearse

LINK = dict(TINY, bench_file='perfbench/fixtures/BENCHMARK.link.json')
CELL = 'tiny-sage-unsup.tiny-link-scan'
FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
READERS = ['link_sample_ms', 'link_negative_ms', 'link_collate_ms',
           'link_train_ms', 'link_unscoped_ms']


@pytest.fixture(scope='module')
def one_cell():
  """The toy dataset, built once: every run of this file sees the same
  graph, rows, seed edges and caps, as every seed of a cell does."""
  _, _, cfg, traffic, _ = run.load_cell(CELL, LINK['bench_file'])
  return homo_link.Cell(cfg, traffic, lambda k, v: None)


@pytest.fixture
def shared_cell(one_cell, monkeypatch):
  monkeypatch.setattr(homo_link, 'Cell', lambda cfg, traffic, log: one_cell)
  return one_cell


@pytest.fixture(scope='module')
def replayed(one_cell):
  """One first call of the toy cell and its replayed chunk."""
  ex = link_scan.Executor(one_cell, one_cell.traffic, 4321)
  first = ex.first_call()
  batches = ex.replay(first['steps'], 2)
  return first, batches, ex.params0


def test_the_reference_imports_nothing_of_the_program():
  with open(reference_homo_link.__file__) as f:
    source = f.read()
  assert 'graphlearn_tpu' not in source
  assert 'optax' not in source


# ------------------------------------------ the family through the drivers


def test_the_link_family_runs_through_the_same_driver(shared_cell, capsys):
  said = {}
  out = rehearse(capsys, CELL, trace=1, fixtures=LINK, said=said)
  assert list(out)[-1] == 'compared' and out['correct'] is True
  assert out['attempted'] > 0 and out['failed'] == 0
  exact = {k: v for k, v in out['compared'].items()
           if k not in control.MEASURED}
  assert set(exact) == {'bad_edges', 'fanout_misses', 'dup_nodes',
                        'bad_rows', 'overflow'} | set(homo_link.LINK_NUMBERS)
  assert all(v == {'value': 0, 'limit': 0} for v in exact.values())
  assert set(control.MEASURED) <= set(out['compared'])
  cell = shared_cell
  # seeds_per_s counts seed EDGES: 16 a step at the toy shape
  win = said['window']
  assert win['seeds'] == win['steps'] * cell.batch == win['steps'] * 16
  # the program's counters over the window: 5 candidates a negative slot
  assert win['link']['link.negatives.tested'] == win['steps'] * 5 * 16
  assert win['link']['link.seeds.unique'] <= win['steps'] * cell.width
  counts = said['valid_counts']
  assert 2 <= counts['nodes'][0] <= cell.width       # rows of the union
  assert counts['buffer_rows'] == cell.node_offsets[-1]
  assert cell.step_flops(counts['nodes'], counts['edges']) > 0
  assert said['seed_width'] == 4 * 16 and said['pairs'] == 32


def _unchanged_state(monkeypatch):
  from graphlearn_tpu.models import train as train_lib
  real = train_lib.make_link_train_step

  def broken(model, tx):
    step, ev = real(model, tx)

    def stuck(state, batch):
      _, loss, acc = step(state, batch)
      return state, loss, acc

    return stuck, ev

  monkeypatch.setattr(train_lib, 'make_link_train_step', broken)


def _half_pairs(monkeypatch):
  """Every second pair left out of the loss, the mean over the rest."""
  from graphlearn_tpu.models import train as train_lib
  real = train_lib.make_link_train_step

  def broken(model, tx):
    step, ev = real(model, tx)
    return (lambda state, b: step(state, dict(
        b, edge_label_index=b['edge_label_index'].at[:, 1::2].set(-1)))), ev

  monkeypatch.setattr(train_lib, 'make_link_train_step', broken)


def _wrong_negative(monkeypatch):
  """A negative sampler that tests nothing: every candidate passes, so an
  edge of the graph can come out as a negative pair — planted by making
  the first candidates the graph's own first edges."""
  import jax.numpy as jnp

  from graphlearn_tpu import ops
  real = ops.random_negative_sample

  def broken(indptr, sorted_indices, *a, **kw):
    out = list(real(indptr, sorted_indices, *a, **kw))
    # slot 0 becomes (row 0's first neighbour): an edge, if row 0 has one
    row = jnp.argmax(indptr[1:] > indptr[:-1]).astype(jnp.int32)
    out[0] = out[0].at[0].set(row)
    out[1] = out[1].at[0].set(sorted_indices[indptr[row]])
    return tuple(out)

  monkeypatch.setattr(ops, 'random_negative_sample', broken)


@pytest.mark.parametrize('fault,caught_by', [
    (_unchanged_state, 'dparam_gap'), (_half_pairs, 'loss_gap_step1'),
    (_wrong_negative, 'false_negatives')])
def test_a_broken_link_path_comes_out_not_correct(
    fault, caught_by, shared_cell, monkeypatch, capsys):
  fault(monkeypatch)
  out = rehearse(capsys, CELL, seed=77, fixtures=LINK)
  row = out['compared'][caught_by]
  assert out['correct'] is False and row['value'] > row['limit']
  if caught_by == 'false_negatives':
    assert out['compared']['bad_pos_pairs']['value'] == 0


def test_control_reads_both_ends_through_the_link_follower(shared_cell,
                                                           capsys):
  """``control.py`` as it stands: program readings under the limits, the
  reference in bfloat16 and both planted faults over them."""
  readings = control.main(
      ['--workload', CELL, '--seeds', '2', '--control-seeds', '1'], **LINK)
  capsys.readouterr()
  _, _, _, _, limits = run.load_cell(CELL, LINK['bench_file'])
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


def _plant(name, batches, cell):
  b_, b = cell.batch, batches[0]
  valid = int(np.asarray(b['num_sampled_nodes']).sum())
  if name == 'bad_pos_pairs':
    # the slot trains on the NEXT seed edge's position
    b['pos'] = np.asarray(b['pos']).copy()
    b['pos'][0] = (b['pos'][0] + 1) % int(cell.indptr[-1])
  elif name == 'false_negatives':
    # the first negative pair points at the first positive's two rows
    eli = np.asarray(b['edge_label_index']).copy()
    eli[:, b_] = eli[:, 0]
    b['edge_label_index'] = eli
  elif name == 'bad_pair_index':
    eli = np.asarray(b['edge_label_index']).copy()
    eli[0, 3] = valid + 5
    b['edge_label_index'] = eli
  elif name == 'bad_labels':
    lab = np.asarray(b['edge_label']).copy()
    lab[-1] = 1
    b['edge_label'] = lab
  elif name == 'seed_repeats':
    batches[1]['pos'] = np.asarray(b['pos']).copy()


@pytest.mark.parametrize('name', homo_link.LINK_NUMBERS)
def test_each_link_number_is_zero_and_trips_on_its_fault(name, one_cell,
                                                         replayed):
  _, batches, _ = replayed
  clean = one_cell.exact_numbers(batches, 2)
  assert set(clean) >= set(homo_link.LINK_NUMBERS)
  assert all(v == 0 for v in clean.values()), clean
  broken = copy.deepcopy(batches)
  _plant(name, broken, one_cell)
  got = one_cell.exact_numbers(broken, 2)
  assert got[name] > 0, got
  # homo_node's five read the subgraph, which no planted fault touched
  assert all(got[k] == 0 for k in ('bad_edges', 'fanout_misses',
                                   'dup_nodes', 'bad_rows', 'overflow'))


def test_the_replay_is_the_chunks_own_batches(one_cell, replayed):
  """The reference over the replayed batches reproduces the first call's
  losses (a chunk that had trained on other pairs could not), positives
  lead the pairs, and the seed list is laid out as the link body says."""
  first, batches, params0 = replayed
  losses, *_ = one_cell.follower(params0, batches)()
  np.testing.assert_allclose(losses, first['losses'][:len(batches)],
                             rtol=1e-5)
  b, b_ = batches[0], one_cell.batch
  assert np.asarray(b['seeds']).shape == (one_cell.width,)
  node, eli = np.asarray(b['node']), np.asarray(b['edge_label_index'])
  np.testing.assert_array_equal(node[eli[0, :b_]], b['seeds'][:b_])
  np.testing.assert_array_equal(node[eli[1, :b_]], b['seeds'][b_:2 * b_])
  assert int(b['link_counts'][0]) == 5 * b_


# ------------------------------------------------- the reference, by hand


def test_the_reference_loss_by_hand():
  """Two nodes, one edge, identity-like weights: the embeddings, the two
  scores and the BCE worked out by hand; every second pair left out is a
  different number; an invalid pair is left out of the mean."""
  import jax.numpy as jnp
  model = dict(kind='sage', in_dim=2, hidden=2, out_dim=2, layers=1, heads=1)
  eye = np.eye(2, dtype=np.float32)
  params = {'params': {'conv0': {
      'lin_self': {'kernel': eye, 'bias': np.zeros(2, np.float32)},
      'lin_nbr': {'kernel': np.zeros((2, 2), np.float32)}}}}
  batch = dict(
      x=np.array([[1., 2.], [3., -1.]], np.float32),
      src=np.array([1], np.int32), tgt=np.array([0], np.int32),
      emask=np.array([True]),
      pair_src=np.array([0, 0, -1], np.int32),
      pair_dst=np.array([1, 0, 1], np.int32),
      pair_label=np.array([1., 0., 1.], np.float32))
  # lin_nbr is zero: h = x. scores: <x0, x1> = 1, <x0, x0> = 5
  bce = lambda s, y: max(s, 0) - s * y + np.log1p(np.exp(-abs(s)))
  want = (bce(1.0, 1.0) + bce(5.0, 0.0)) / 2
  step = reference_homo_link.make_step(model, 0.0)
  zeros = {'params': {'conv0': {
      'lin_self': {'kernel': 0 * eye, 'bias': np.zeros(2, np.float32)},
      'lin_nbr': {'kernel': 0 * eye}}}}
  *_, loss, grads = step(params, zeros, zeros, 0, batch)
  assert float(loss) == pytest.approx(want, rel=1e-6)
  assert float(jnp.abs(grads['params']['conv0']['lin_self']['kernel'])
               .sum()) > 0
  half = reference_homo_link.make_step(model, 0.0, half_batch=True)
  *_, loss_half, _ = half(params, zeros, zeros, 0, batch)
  # pairs 0 and 2 are kept, pair 2 is invalid: the mean is pair 0's alone
  assert float(loss_half) == pytest.approx(bce(1.0, 1.0), rel=1e-6)
  np.testing.assert_allclose(
      reference_homo_link.bce_with_logits(jnp.array([-3., 0., 4.]),
                                          jnp.array([1., 1., 0.])),
      [bce(-3., 1.), bce(0., 1.), bce(4., 0.)], rtol=1e-6)


def test_the_link_operation_count_by_hand():
  model = dict(kind='sage', in_dim=100, hidden=256, out_dim=256, layers=3,
               heads=1)
  nodes, edges = [2000, 20000, 70000, 80000], [30000, 200000, 350000]
  base = flops.step_flops(model, nodes, edges)
  got = flops_homo_link.step_flops(model, nodes, edges, 1024)
  assert got - base == 3 * 2 * 1024 * 256
  # the last layer is 256 wide over the seed union, where a node job's is
  # its class count wide over the seeds
  narrow = flops.step_flops(dict(model, out_dim=47), nodes, edges)
  assert base - narrow == 3 * 2 * 2 * 2000 * 256 * (256 - 47)


# --------------------------------------- the readers on a recorded v5e trace


def _run_from(trace_file, steps, window=None):
  device, host = trace_reduce.load(os.path.join(FIX, trace_file))
  busy_s, window_s, _ = trace_reduce.busy(device)
  slice_ = dict(device=device, host=host, steps=steps, busy_s=busy_s,
                window_s=window_s)
  return dict(cell=None, traffic={}, peaks={}, counts={},
              window=window or dict(steps=0, wall_s=0.0), scan=slice_)


def _read(name, run_):
  return importlib.import_module(
      f'perfbench.layer_metrics.{name}').read(run_)


def test_the_link_readers_on_a_recorded_v5e_link_chunk(capsys):
  with open(os.path.join(FIX, 'trace_v5e_link_cut.expected.json')) as f:
    want = json.load(f)
  run_ = _run_from('trace_v5e_link_cut.json', want['steps'])
  got = {name: _read(name, run_) for name in READERS}
  for name, v in want['metrics'].items():
    assert got[name] == pytest.approx(v, rel=1e-9), name
  line = [json.loads(l[len('perfbench: '):])
          for l in capsys.readouterr().out.splitlines()
          if l.startswith('perfbench: {"link_reduce"')]
  assert len(line) == 1                       # reduced once, said once
  split = line[0]['link_reduce']
  assert set(split) == {'glt.sample/seeds', 'glt.sample/negative',
                        'glt.sample/union', 'glt.train/pairs'}
  for k, v in want['link_reduce'].items():
    assert split[k] == pytest.approx(v, rel=1e-9), k
  # the new mechanism's cost is a part of the sampling layer, and the four
  # layers add up to the chunk program's busy time
  assert got['link_negative_ms'] == pytest.approx(
      sum(v for k, v in split.items() if k.startswith('glt.sample/')))
  assert got['link_negative_ms'] < got['link_sample_ms']
  assert split['glt.train/pairs'] < got['link_train_ms']
  scopes, _ = scope_reduce.by_scope(run_['scan']['device'],
                                    scope_reduce.CHUNK_STEM)
  assert sum(got[n] for n in READERS if n != 'link_negative_ms') == \
      pytest.approx(1e3 * sum(scopes.values()) / want['steps'], rel=1e-9)


def test_the_link_readers_find_nothing_in_a_node_program():
  """Over a node cell's chunk (the recorded scan trace) the link split and
  its own reader return None — never 0; the layer readers read the layers
  whatever program it is."""
  run_ = _run_from('trace_v5e_scan_cut.json', 2)
  assert link_reduce.split(run_) is None
  assert _read('link_negative_ms', run_) is None
  assert _read('link_negative_reject_share', run_) is None
  assert _read('link_sample_ms', run_) > 0


@pytest.mark.parametrize('window,want', [
    (dict(link={'link.negatives.tested': 2560 * 64,
                'link.negatives.rejected': 3}), 100.0 * 3 / (2560 * 64)),
    (dict(link={'link.negatives.tested': 0}), None),
    (dict(), None)])
def test_the_reject_share_reads_the_windows_counters(window, want):
  run_ = dict(window=window)
  got = _read('link_negative_reject_share', run_)
  assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize('path,want', [
    (('glt.sample', 'negative', 'jit(random_negative_sample)', 'sort'),
     'glt.sample/negative'),
    (('glt.sample', 'union', 'concatenate'), 'glt.sample/union'),
    (('glt.sample', 'seeds', 'while', 'body', 'xor'), 'glt.sample/seeds'),
    (('glt.train', 'fwd_bwd', 'transpose(jvp(pairs))', 'mul'),
     'glt.train/pairs'),
    (('glt.train', 'fwd_bwd', 'jvp(pairs)', 'gather'), 'glt.train/pairs'),
    (('glt.train', 'fwd_bwd', 'pairs', 'gather'), 'glt.train/pairs'),
    (('glt.train', 'fwd_bwd', 'jvp(GraphSAGE)', 'conv2', 'dot'), None),
    (('glt.sample', 'hop1', 'draw', 'gather'), None),
    (('glt.train', 'update', 'add'), None), ((), None)])
def test_link_scope_names_the_four_scopes_and_nothing_else(path, want):
  assert link_reduce.link_scope(path) == want
