"""Family ``hetero_table_node``: a typed graph whose non-labelled node types
have learned tables — its ``Cell``, plain reference, ``table_scan``
executor and five ``table_*`` readers, added as files and run through
``run.main`` and ``control.main`` as they stand.

These test the yardstick: the reference against the program's ``RGNN``
with tables on seeded weights, the whole command at a toy shape with
``correct`` true and every exact number 0, false under a planted fault in
the tables' own path, the counts by hand, the readers on a hand-cut
recorded v5e trace of the cell's chunk, and the cell's entries in
``BENCHMARK.json``.
"""
import importlib
import json
import os

import numpy as np
import pytest

from perfbench import control, flops_hetero_table_node, run, table_reduce
from perfbench import reference_hetero_table_node as reference
from perfbench.families import hetero_table_node
from test_perfbench import TINY, _unchanged_state, rehearse

TABLE = dict(TINY, bench_file='perfbench/fixtures/BENCHMARK.table.json')
CELL = 'tiny-rgcn.tiny-embed-scan'
REAL = 'rgcn-mag.embed-scan-exact'
FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
READERS = ['table_gather_ms', 'table_scatter_ms', 'table_update_ms',
           'table_rows_per_step', 'table_update_roofline']


@pytest.fixture(scope='module')
def one_cell():
  _, _, cfg, traffic, _ = run.load_cell(CELL, TABLE['bench_file'])
  return hetero_table_node.Cell(cfg, traffic, lambda k, v: None)


@pytest.fixture
def shared_cell(one_cell, monkeypatch):
  monkeypatch.setattr(hetero_table_node, 'Cell',
                      lambda cfg, traffic, log: one_cell)
  return one_cell


def test_the_reference_imports_nothing_of_the_program():
  with open(reference.__file__) as f:
    source = f.read()
  assert 'graphlearn_tpu' not in source and 'from perfbench' not in source


def test_the_generator_makes_cites_undirected_and_rows_for_paper_only(
    one_cell):
  """``cites`` is the drawn edges and their reverses (twice the drawn
  count, symmetric as a multiset); only ``paper`` has rows; every other
  type gets a table of ``table_dim``; the directed edges add up to the
  configuration's count."""
  cell = one_cell
  d = cell.cfg['dataset']
  indptr, indices = cell.csr[('paper', 'cites', 'paper')]
  e = d['relations']['paper__cites__paper']['edges']
  assert indices.size == 2 * e
  src = np.repeat(np.arange(indptr.size - 1), np.diff(indptr))
  fwd = np.sort(src.astype(np.int64) * 10 ** 6 + indices)
  rev = np.sort(indices.astype(np.int64) * 10 ** 6 + src)
  np.testing.assert_array_equal(fwd, rev)
  assert set(cell.feat) == {'paper'}
  assert cell.tables == {t: (n, d['table_dim'])
                         for t, n in d['node_types'].items() if t != 'paper'}
  assert sum(ix.size for _, ix in cell.csr.values()) == \
      d['num_directed_edges']


def _program_run(cell, seed, steps):
  """The program's ``RGNN`` with tables over the per-batch typed loader:
  per-step losses, the first gradient, params and first moment after
  ``steps``, and the batches as the reference takes them."""
  import jax

  from graphlearn_tpu.models import train as train_lib
  model = cell.make_model()
  state, tx, params0 = cell.make_state(model, seed)
  loss_fn = train_lib.make_loss_fn(model, cell.num_classes)
  step, _ = train_lib.make_train_step(model, tx, cell.num_classes)
  losses, batches, g0 = [], [], None
  for _, b in zip(range(steps), cell.make_loader(seed)):
    d = train_lib.batch_to_dict(b)
    for t in cell.table_types:                   # ids, not rows
      assert np.asarray(d['x'][t]).dtype == np.int32
    batches.append(jax.device_get(dict(
        node=b.node, edge_index=b.edge_index, edge_mask=b.edge_mask,
        num_sampled_nodes=b.num_sampled_nodes)))
    if g0 is None:
      g0 = jax.device_get(jax.grad(lambda p: loss_fn(p, d)[0])(state.params))
    state, loss, _ = step(state, d)
    losses.append(float(loss))
  return (np.array(losses), g0, jax.device_get(state.params),
          jax.device_get(state.opt_state[0].mu), params0, batches)


def _gaps(prog, ref):
  import jax
  p = [np.asarray(x, np.float64) for x in jax.tree.leaves(prog)]
  r = [np.asarray(x, np.float64) for x in jax.tree.leaves(ref)]
  rn = np.array([np.linalg.norm(x) for x in r])
  return np.array([np.linalg.norm(a - b) for a, b in zip(p, r)]) / \
      np.maximum(rn, np.median(rn))


def test_rgnn_with_tables_matches_the_reference(one_cell):
  """``RGNN(conv='sage', node_tables=...)`` and the plain reference from
  the same seeded weights and tables over the same three batches: loss to
  1e-5 relative, the first gradient (each table's whole) to 1e-4 of a
  leaf's norm, the first moment and the parameters after three dense Adam
  steps to 1e-3 (float32 on XLA:CPU; Adam divides by sqrt(v))."""
  cell, seed = one_cell, 4321
  losses, g0, params, mu, params0, batches = _program_run(cell, seed, 3)
  rl, rg0, rparams, rmu = cell.follower(params0, batches)()
  assert (np.abs(losses - rl) / np.abs(rl)).max() < 1e-5
  assert _gaps(g0, rg0).max() < 1e-4
  assert _gaps(mu, rmu).max() < 1e-3
  assert _gaps(params, rparams).max() < 1e-3
  for t in cell.table_types:      # every row of every table moved alike
    np.testing.assert_allclose(params['params']['node_tables'][t],
                               rparams['params']['node_tables'][t],
                               rtol=1e-3, atol=1e-5)


def test_the_layer_plan_and_the_tree_are_the_programs(one_cell):
  md = one_cell.model_desc
  rels = reference.layer_relations(md)
  assert len(rels) == md['layers'] == 2
  assert set(md['tables']) == set(one_cell.table_types) == {
      'author', 'field_of_study', 'institution'}
  assert all(md['relations'][n][1] == 'paper' for n in rels[-1])
  one_cell.make_state(one_cell.make_model(), 5)   # raises on a mismatch


# ------------------------------ the family through the drivers, as is

def _exact(out):
  return {k: v for k, v in out['compared'].items()
          if k not in control.MEASURED}


def test_the_table_family_runs_through_the_same_driver(shared_cell, capsys):
  said = {}
  out = rehearse(capsys, CELL, trace=1, fixtures=TABLE, said=said)
  assert list(out)[-1] == 'compared' and out['correct'] is True
  assert out['attempted'] > 0 and out['failed'] == 0
  cell = shared_cell
  name = hetero_table_node.name_of
  assert set(_exact(out)) == (
      {f'{k}.{name(et)}' for et in cell.etypes
       for k in ('bad_edges', 'fanout_misses')} |
      {f'dup_nodes.{t}' for t in cell.ntypes} |
      {f'bad_ids.{t}' for t in cell.table_types} |
      {'bad_rows.paper', 'pad_grad_rows', 'overflow'})
  assert all(v == {'value': 0, 'limit': 0} for v in _exact(out).values())
  counts = said['valid_counts']
  assert counts['nodes'][0] == cell.batch
  assert cell.step_flops(counts['nodes'], counts['edges']) > 0
  assert said['window']['table']['table.rows_read'] > 0


def _misrouted_scatter(monkeypatch):
  """The gather's transpose lands each row's gradient on the next row:
  rows no slot read get a gradient."""
  import functools

  import jax
  import jax.numpy as jnp

  from graphlearn_tpu.models import tables
  real = tables.table_gather

  @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
  def gather(n, table, ids):
    return real(table, ids)

  def fwd(n, table, ids):
    return real(table, ids), ids

  def bwd(n, ids, ct):
    slots = jnp.where(ids >= 0, (ids + 1) % n, n)
    return jnp.zeros((n, ct.shape[-1]), ct.dtype).at[slots].add(
        ct, mode='drop'), None

  gather.defvjp(fwd, bwd)
  monkeypatch.setattr(tables, 'table_gather', lambda table, ids: gather(
      table.shape[0], jnp.asarray(table), ids))


def _shifted_ids(monkeypatch):
  """The collate emits each table type's ids one off."""
  from graphlearn_tpu import ops
  real = ops.collate_typed_batch

  def broken(*a, id_types=(), **kw):
    x, ei, y = real(*a, id_types=id_types, **kw)
    return dict(x, **{t: x[t] + (x[t] >= 0) for t in id_types}), ei, y
  monkeypatch.setattr(ops, 'collate_typed_batch', broken)


@pytest.mark.parametrize('fault,caught_by', [
    (_unchanged_state, 'dparam_gap'),
    (_misrouted_scatter, 'pad_grad_rows'),
    (_shifted_ids, 'bad_ids.author')])
def test_a_broken_table_path_comes_out_not_correct(
    fault, caught_by, shared_cell, monkeypatch, capsys):
  fault(monkeypatch)
  out = rehearse(capsys, CELL, seed=77, fixtures=TABLE)
  row = out['compared'][caught_by]
  assert out['correct'] is False and row['value'] > row['limit']


def test_control_reads_the_table_cells_limits(shared_cell, capsys):
  readings = control.main(['--workload', CELL, '--seeds', '1',
                           '--control-seeds', '1'], **TABLE)
  capsys.readouterr()
  limits = run.load_cell(CELL, TABLE['bench_file'])[-1]
  by = {r['kind']: r for r in readings}
  passes = lambda r: all(r[k] <= limits[k] for k in control.MEASURED)
  assert passes(by['program'])
  assert not passes(by['control_ref_bf16'])
  assert not passes(by['fault_half_batch'])
  assert not passes(by['fault_state_unchanged'])


# ------------------------------------------- the counts, by hand arithmetic

def test_table_flops_and_bytes_by_hand():
  model = dict(
      kind='rgcn', hidden=4, out_dim=3, layers=2, out_ntype='p',
      ntypes=['a', 'p'], in_dims={'a': 6, 'p': 8}, tables={'a': [10, 6]},
      relations={'p__c__p': ('p', 'p'), 'p__w__a': ('a', 'p'),
                 'a__rw__p': ('p', 'a')},
      hop_relations=[['p__c__p', 'p__w__a'],
                     ['a__rw__p', 'p__c__p', 'p__w__a']])
  nodes = {'p': [2, 5, 9], 'a': [0, 3, 4]}
  edges = {'p__c__p': [6, 20], 'p__w__a': [4, 11], 'a__rw__p': [0, 7]}
  mm = lambda n, d_in, k: 2 * n * d_in * 4 * k
  want = (
      # layer 0 (2 hops): targets within 1 hop; a paper row is data (x2),
      # a table row and a hidden row need their gradient (x3)
      mm(3, 6, 3) + mm(3, 8, 2) + 2 * 7 * 8 +      # a__rw__p: p -> a
      mm(7, 8, 2) + mm(7, 8, 2) + 2 * 26 * 8 +     # p__c__p
      mm(7, 8, 2) + mm(7, 6, 3) + 2 * 15 * 6 +     # p__w__a: a -> p
      # layer 1 (1 hop): targets the seeds
      mm(2, 4, 3) + mm(2, 4, 3) + 2 * 6 * 4 +
      mm(2, 4, 3) + mm(2, 4, 3) + 2 * 4 * 4 +
      3 * 2 * 2 * 4 * 3)                          # the classifier
  assert flops_hetero_table_node.step_flops(model, nodes, edges) == want
  assert flops_hetero_table_node.table_update_bytes(
      {'a': (10, 6), 'b': (3, 2)}) == 7 * (60 + 6) * 4


# ------------------------------ the readers on a recorded v5e trace

@pytest.mark.parametrize('path, want', [
    (('glt.train', 'fwd_bwd', 'jvp(table)', 'gather', 'select_n'), 'gather'),
    (('glt.train', 'fwd_bwd', 'transpose(glt.train)', 'fwd_bwd',
      'jvp(table)', 'scatter', 'scatter-add'), 'scatter'),
    (('glt.train', 'update', 'table', 'add'), 'update'),
    (('glt.train', 'update', 'add'), None),
    (('glt.train', 'fwd_bwd', 'jvp(hetero0)', 'dot_general'), None),
    (('glt.collate', 'paper', 'gather'), None), ((), None)])
def test_table_scope_names_parse(path, want):
  assert table_reduce.table_part(path) == want


class _TableCell:
  def __init__(self, need):
    self._need = need

  def table_update_bytes(self):
    return self._need


def test_table_readers_on_a_recorded_v5e_chunk(capsys):
  from perfbench import trace_reduce
  with open(os.path.join(FIX, 'trace_v5e_table_cut.expected.json')) as f:
    want = json.load(f)
  device, host = trace_reduce.load(
      os.path.join(FIX, 'trace_v5e_table_cut.json'))
  busy_s, window_s, _ = trace_reduce.busy(device)
  slice_ = dict(device=device, host=host, steps=want['steps'],
                busy_s=busy_s, window_s=window_s)
  run_ = dict(cell=_TableCell(want['update_bytes']), traffic={},
              window=dict(steps=want['window_steps'],
                          table={'table.rows_read': want['rows_read']}),
              peaks=dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12),
              counts=dict(nodes=[1], edges=[], buffer_rows=1), scan=slice_)
  got = {name: importlib.import_module(
      f'perfbench.layer_metrics.{name}').read(run_) for name in READERS}
  for name in READERS:
    assert got[name] == pytest.approx(want['readers'][name], rel=1e-9), name
  assert 0 < got['table_update_roofline'] <= 100
  said = [l for l in capsys.readouterr().out.splitlines()
          if l.startswith('perfbench: {"table_reduce"')]
  assert len(said) == 1
  # a slice without the tables' scopes: nothing is read, nothing is said
  plain_dev, plain_host = trace_reduce.load(
      os.path.join(FIX, 'trace_v5e_typed_cut.json'))
  b, w, _ = trace_reduce.busy(plain_dev)
  plain = dict(cell=_TableCell(1), window=dict(steps=1),
               peaks=run_['peaks'],
               scan=dict(device=plain_dev, host=plain_host, steps=1,
                         busy_s=b, window_s=w))
  for name in READERS:
    assert importlib.import_module(
        f'perfbench.layer_metrics.{name}').read(dict(plain)) is None, name


# -------------------------------------------- the cell in BENCHMARK.json

def test_the_configuration_and_its_cell_are_in_the_benchmark():
  """``BENCHMARK.json`` gained the configuration (nothing ``reduced``: the
  published counts whole), its one one-chip cell and the five readers,
  each listing that cell alone; the readers' toy entries are the same."""
  with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  conf = {c['name']: c for c in bench['configs']}['rgcn-mag']
  assert conf['reduced'] == [] and conf['file'].endswith('rgcn-mag.json')
  cell = {w['name']: w for w in bench['workloads']}[REAL]
  assert (cell['config'], cell['traffic'], cell['chips']) == (
      'rgcn-mag', 'embed-scan-exact', 1)
  by = {m['name']: m for m in bench['per_layer']}
  for name in READERS:
    assert by[name]['workloads'] == [REAL]
    assert by[name]['moves'] == 'seeds_per_s'
  _, _, cfg, traffic, limits = run.load_cell(REAL, 'BENCHMARK.json')
  d = cfg['dataset']
  directed = sum((2 if r.get('undirected') or 'reverse' in r else 1) *
                 r['edges'] for r in d['relations'].values())
  assert directed == d['num_directed_edges'] == 42_222_014
  assert traffic['reference_steps'] == traffic['chunk_size'] == 16
  assert cfg['steps_per_call'] % traffic['chunk_size'] == 0
  assert {'pad_grad_rows', 'bad_rows.paper'} <= set(limits)
  assert {f'bad_ids.{t}' for t in ('author', 'field_of_study',
                                   'institution')} <= set(limits)
