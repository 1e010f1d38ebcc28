"""The seam PR 28 cut: ``perfbench/run.py`` and ``perfbench/control.py``
reach the graph, the model, the reference and the counts only through the
``Cell`` a family built and the ``Executor`` a traffic mix names.

Held three ways: a test-only second family that is no homogeneous CSR and
no SAGE or GAT (``perfbench/fixtures/two_table.py``, its reference beside
it, its own executor ``pair_loop.py``) goes through ``run.main`` and
``control.main`` untouched and fails under a planted fault; the ``tiny-*``
rehearsals of the first family print what the calls the parent made in
``run.py`` itself give; and the two drivers' sources name nothing of a
graph or a model.
"""
import importlib
import json
import os
import re
import sys

import numpy as np
import pytest

from perfbench import check, control, reference, run
from test_perfbench import TINY, rehearse

TOY = dict(TINY, bench_file='perfbench/fixtures/BENCHMARK.toy.json')
TOY_CELL = 'two-table.pair-loop'
TOY_EXACT = {'bad_pairs', 'bad_user_rows', 'bad_item_rows'}


@pytest.fixture
def second_family(monkeypatch):
  """The toy family and its executor under the names their files state,
  as a later PR's ``families/<f>.py`` and ``executors/<e>.py`` would be."""
  for package, name in (('families', 'two_table'),
                        ('executors', 'pair_loop')):
    monkeypatch.setitem(
        sys.modules, f'perfbench.{package}.{name}',
        importlib.import_module(f'perfbench.fixtures.{name}'))


def test_a_second_family_runs_through_the_same_driver(second_family, capsys):
  out = rehearse(capsys, TOY_CELL, trace=1, fixtures=TOY)
  assert list(out)[-1] == 'compared' and out['correct'] is True
  assert out['attempted'] > 0 and out['failed'] == 0
  assert set(out['compared']) == TOY_EXACT | set(control.MEASURED)
  assert all(out['compared'][k] == {'value': 0, 'limit': 0}
             for k in TOY_EXACT)


def _wrong_item_rows(monkeypatch):
  import jax.numpy as jnp

  from graphlearn_tpu import ops
  real = ops.gather_rows

  def broken(table, id2index, ids):
    rows = real(table, id2index, ids)
    return jnp.roll(rows, 1, axis=0) if table.shape[1] == 10 else rows

  monkeypatch.setattr(ops, 'gather_rows', broken)


def _unchanged_state(monkeypatch):
  import optax
  monkeypatch.setattr(optax, 'apply_updates', lambda params, updates: params)


@pytest.mark.parametrize('fault,caught_by', [
    (_wrong_item_rows, 'bad_item_rows'), (_unchanged_state, 'dparam_gap')])
def test_the_second_family_fails_under_a_planted_fault(
    fault, caught_by, second_family, monkeypatch, capsys):
  fault(monkeypatch)
  out = rehearse(capsys, TOY_CELL, seed=77, fixtures=TOY)
  row = out['compared'][caught_by]
  assert out['correct'] is False and row['value'] > row['limit']


def test_control_reads_a_second_familys_limits(second_family, capsys):
  readings = control.main(['--workload', TOY_CELL, '--seeds', '2',
                           '--control-seeds', '2'], **TOY)
  capsys.readouterr()
  limits = run.load_cell(TOY_CELL, TOY['bench_file'])[-1]
  passes = lambda r: all(r[k] <= limits[k] for k in control.MEASURED)
  for r in readings:
    assert passes(r) == (r['kind'] in ('program',
                                       'look_ref_default_precision')), r
  assert {r['kind'] for r in readings} >= {
      'program', 'control_ref_bf16', 'fault_half_batch',
      'fault_state_unchanged'}
  assert all(r[k] == 0 for r in readings if r['kind'] == 'program'
             for k in TOY_EXACT)


@pytest.mark.parametrize('workload', ['tiny-sage.tiny-scan',
                                      'tiny-gat.tiny-scan'])
def test_the_first_family_compares_what_the_parent_compared(workload,
                                                            capsys):
  """The seam changes no number: ``run.main``'s ``compared`` values are,
  to the last digit, what the calls that stood in the parent's ``run.py``
  (``check.validate_batches`` on the cell; ``reference_batch`` and
  ``reference.follow`` on its ``model_desc``) give on the same seed."""
  seed = 2_800_000_011
  said = {}
  out = rehearse(capsys, workload, seed=seed, trace=1, said=said)
  o = run.open_cell(workload, TINY['bench_file'], 'cpu')
  traffic = o['traffic']
  cell = o['family'].Cell(o['cfg'], traffic, lambda k, v: None)
  ex = o['executor'].Executor(cell, traffic, seed)
  first = ex.first_call()
  n_val = int(traffic['validated_batches'])
  batches = ex.replay(first['steps'], n_val)
  counts, params0 = ex.valid_counts(), ex.params0
  ex.free()
  # the parent's own lines
  numbers = check.validate_batches(cell, batches, n_val)
  ref_in = [cell.reference_batch(b['node'], b['edge_index'], b['edge_mask'])
            for b in batches]
  numbers.update(check.compare_training(
      first, params0, *reference.follow(cell.model_desc, cell.lr,
                                        cell.batch, params0, ref_in)))
  assert {k: v['value'] for k, v in out['compared'].items()} == \
      {k: numbers[k] for k in out['compared']}
  assert list(out['compared']) == [
      'bad_edges', 'fanout_misses', 'dup_nodes', 'bad_rows', 'overflow',
      'loss_gap', 'dparam_gap', 'moment_gap', 'loss_gap_step1']
  # the counts are the replayed chunk's: the window's own first batches
  nsn = np.array([b['num_sampled_nodes'] for b in batches], float)
  assert len(batches) == traffic['chunk_size']
  assert counts['nodes'] == pytest.approx(nsn.mean(0).reshape(-1))
  assert counts['nodes'][0] == cell.batch
  assert sum(counts['edges']) == pytest.approx(
      np.mean([b['edge_mask'].sum() for b in batches]))
  assert counts == said['valid_counts']


NAMES_OF_A_GRAPH_OR_A_MODEL = [
    'indptr', 'model_desc', 'validate_batches', 'reference.follow',
    'reference_batch', 'executors.step', 'step_mod', 'homo']


@pytest.mark.parametrize('word', NAMES_OF_A_GRAPH_OR_A_MODEL)
@pytest.mark.parametrize('driver', ['run.py', 'control.py'])
def test_the_drivers_name_no_graph_no_model_and_no_executor(driver, word):
  with open(os.path.join(run.ROOT, 'perfbench', driver)) as f:
    source = f.read()
  assert word not in source
  # nor any family or executor module by import
  assert not re.search(r'perfbench\.(families|executors)\.[a-z]', source)
  assert not re.search(r'from perfbench\.(families|executors) import',
                       source)
