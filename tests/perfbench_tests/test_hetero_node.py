"""Family ``hetero_node`` (PR 30): the typed graph's ``Cell``, its plain
reference, its executor and its five readers — added as files, run through
``run.main`` and ``control.main`` as they stand.

Like the other files here these test the yardstick: the reference against
the program's ``RGNN`` on seeded weights (and that the program's bfloat16
path falls outside the same tolerances), the whole command at a toy typed
shape with ``correct`` true, false under two planted faults and under one
wrong edge planted in one edge type, the typed FLOP and byte counts by
hand, and the typed readers on a hand-cut recorded v5e trace of the typed
slice.
"""
import importlib
import json
import os

import numpy as np
import pytest

from perfbench import check, control, flops_hetero_node, run, typed_reduce
from perfbench import reference_hetero_node as reference
from perfbench.families import hetero_node
from test_perfbench import TINY, _half_batch, _unchanged_state, rehearse

TYPED = dict(TINY, bench_file='perfbench/fixtures/BENCHMARK.typed.json')
CELL = 'tiny-rgat.tiny-typed-scan'
FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
READERS = ['typed_sample_ms', 'typed_collate_ms', 'typed_train_ms',
           'typed_unscoped_ms', 'typed_collate_roofline', 'typed_draw_ms',
           'typed_draw_tiles_per_step', 'typed_host_gap_ms']


@pytest.fixture(scope='module')
def one_cell():
  """The toy typed dataset, built once: every run of this file sees the
  same graph, tables and caps, as every seed of a cell does."""
  _, _, cfg, traffic, _ = run.load_cell(CELL, TYPED['bench_file'])
  return hetero_node.Cell(cfg, traffic, lambda k, v: None)


@pytest.fixture
def shared_cell(one_cell, monkeypatch):
  monkeypatch.setattr(hetero_node, 'Cell', lambda cfg, traffic, log: one_cell)
  return one_cell


def test_the_reference_imports_nothing_of_the_program():
  with open(reference.__file__) as f:
    source = f.read()
  assert 'graphlearn_tpu' not in source and 'from perfbench' not in source


# ----------------------------------- (b) RGNN against the plain reference

def _program_run(cell, seed, steps, dtype=None):
  """The program's ``RGNN`` over the per-batch typed loader: per-step
  losses, the first gradient, params and first moment after ``steps``."""
  import jax

  from graphlearn_tpu.models import train as train_lib
  model = cell.make_model(dtype)
  state, tx, params0 = cell.make_state(model, seed)
  loss_fn = train_lib.make_loss_fn(model, cell.num_classes)
  step, _ = train_lib.make_train_step(model, tx, cell.num_classes)
  losses, batches, g0 = [], [], None
  for _, b in zip(range(steps), cell.make_loader(seed)):
    d = train_lib.batch_to_dict(b)
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
  """Per-leaf relative gaps of two trees, by the reference leaf's norm
  (or the median leaf's where a leaf is nearly zero)."""
  import jax
  p = [np.asarray(x, np.float64) for x in jax.tree.leaves(prog)]
  r = [np.asarray(x, np.float64) for x in jax.tree.leaves(ref)]
  rn = np.array([np.linalg.norm(x) for x in r])
  return np.array([np.linalg.norm(a - b) for a, b in zip(p, r)]) / \
      np.maximum(rn, np.median(rn))


def test_rgnn_gat_merge_dense_matches_the_reference(one_cell):
  """``RGNN(conv='gat', merge_dense=True)`` and the plain reference from
  the same seeded weights over the same three typed batches. Tolerances,
  each with its reason (float32 on XLA:CPU, where both multiply exactly):

  * loss, 2e-6 relative: two summation orders of one float32 forward (the
    k-run dense softmax against segment sums) — a few units in the last
    place of a loss near 2.4;
  * first gradient, 2e-5 of a leaf's norm: the same through the backward
    pass, where sums over edges are re-ordered once more;
  * the norm of the first moment after 3 steps, 2e-3, and of the
    parameters' change, 1e-2 (``check.py``'s measure, leaf by leaf): the
    moment is a decayed sum of gradients and inherits their agreement;
    the change is Adam's, which divides by sqrt(v) of three steps, so an
    entry whose gradient is near zero moves by round-off alone, and on
    tables this small such entries are a visible share of a leaf.

  The program's own bfloat16 path (``RGNN(dtype=bfloat16)``) must fall
  outside them: its loss misses by 1e-3 and its gradient by 1e-2."""
  import jax.numpy as jnp
  cell, seed, steps = one_cell, 1234, 3
  losses, g0, params, mu, params0, batches = _program_run(cell, seed, steps)
  rl, rg0, rparams, rmu = cell.follower(params0, batches)()
  loss_gap = np.abs(losses - rl) / np.abs(rl)
  assert loss_gap.max() < 2e-6, loss_gap
  assert _gaps(g0, rg0).max() < 2e-5
  import jax
  dp = jax.tree.map(lambda a, b: a - b, params, params0)
  rdp = jax.tree.map(lambda a, b: a - b, rparams, params0)
  # by check.py's own measure: the gap between the two changes' norms,
  # leaf by leaf, the leaves the first gradient barely touches left out
  # (an entry whose gradient is round-off takes a full Adam step either
  # way, so entry by entry the two states differ by 2 * lr there)
  g0n = np.array([np.linalg.norm(x) for x in jax.tree.leaves(rg0)])
  moved = g0n >= 1e-3 * np.median(g0n)
  assert moved.sum() >= 0.8 * moved.size
  assert check._worst_leaf_gap(check._leaves(dp), check._leaves(rdp),
                               moved) < 1e-2
  assert check._worst_leaf_gap(check._leaves(mu), check._leaves(rmu)) < 2e-3
  # the program's bfloat16 path is told apart by the same tolerances
  bl, bg0, *_ = _program_run(cell, seed, steps, dtype=jnp.bfloat16)
  assert (np.abs(bl - rl) / np.abs(rl)).max() > 2e-6
  assert _gaps(bg0, rg0).max() > 2e-5


@pytest.mark.parametrize('blocks', [(8, 16, 8), (16, 8, 32)])
def test_the_reference_in_blocks_equals_the_reference_in_one_piece(
    one_cell, blocks, monkeypatch):
  """What lets the plain reference fit the chip at the timed size — row
  blocks under the input ``Linear``s, edge blocks under each relation's
  messages, batches cut to their valid rows and padded to one size —
  changes the order of two sums and nothing else: over the same three
  typed batches from the same weights, the blocked run (blocks far
  smaller than the batch, so every relation takes several) and the
  one-piece run (blocks larger than any batch) agree as two float32
  summation orders do — 1e-6 on the losses, 1e-5 of a leaf's norm on the
  first gradient and on the first moment after three steps, 1e-4 on the
  parameters (Adam divides by sqrt(v): an entry whose gradient is
  round-off takes a full step of lr either way, so the parameters agree
  one digit less than the gradients that moved them). The half-batch
  fault and the bfloat16 control go through the same blocks."""
  cell = one_cell
  *_, params0, batches = _program_run(cell, 99, 3)
  follow = cell.follower(params0, batches)
  for name in ('EMBED_BLOCK', 'EDGE_BLOCK', 'PAD_TO'):
    monkeypatch.setattr(reference, name, 1 << 20)
  whole = follow()
  for name, size in zip(('EMBED_BLOCK', 'EDGE_BLOCK', 'PAD_TO'), blocks):
    monkeypatch.setattr(reference, name, size)
  rows, edges = reference.padded_sizes(
      [cell.reference_batch(b) for b in batches])
  assert max(edges.values()) > 2 * blocks[1]      # several edge blocks
  assert max(rows.values()) > 2 * blocks[0]       # several row blocks
  cut = follow()
  assert np.abs(cut[0] - whole[0]).max() < 1e-6 * np.abs(whole[0]).max()
  assert _gaps(cut[1], whole[1]).max() < 1e-5
  assert _gaps(cut[2], whole[2]).max() < 1e-4
  assert _gaps(cut[3], whole[3]).max() < 1e-5
  low = follow(compute_dtype='bfloat16')
  assert np.abs(low[0] - whole[0]).max() > 1e-4 * np.abs(whole[0]).max()
  half = follow(half_batch=True)
  assert _gaps(half[1], whole[1]).max() > 1e-2


def test_the_reference_takes_valid_rows_and_edges_only(one_cell):
  """``reference_batch`` hands the reference a batch's valid prefix of
  each node buffer and its valid edges in slot order, with the count of
  edges in hops ``< h``: every edge's two ends are positions in those
  prefixes, and the counts are the masks' own."""
  cell = one_cell
  *_, batches = _program_run(cell, 5, 1)
  b = batches[0]
  r = cell.reference_batch(b)
  for t in cell.ntypes:
    n = int(np.sum(b['num_sampled_nodes'][t]))
    assert len(r['node'][t]) == n <= len(b['node'][t])
    assert (np.asarray(r['node'][t]) >= 0).all()
  for et in cell.etypes:
    name = hetero_node.name_of(et)
    e, em = r['edges'][name], np.asarray(b['edge_mask'][cell.out_et[et]])
    assert len(e['src']) == len(e['tgt']) == em.sum() == e['hops'][-1]
    assert e['hops'][0] == 0 and list(e['hops']) == sorted(e['hops'])
    s_t, d_t = cell.model_desc['relations'][name]
    assert (e['src'] < len(r['node'][s_t])).all()
    assert (e['tgt'] < len(r['node'][d_t])).all()


def test_the_typed_layer_plan_is_the_programs(one_cell):
  """``reference.layer_relations`` — which relations pass messages at
  which layer — is read off the plan, and is what ``RGNN`` builds
  parameters for (``make_state`` compares the two trees leaf by leaf)."""
  md = one_cell.model_desc
  rels = reference.layer_relations(md)
  assert len(rels) == md['layers'] == 2
  assert set(rels[0]) == {hetero_node.name_of(et)
                          for et in one_cell.etypes}
  # the last layer reads hop 0 alone: the seed type's own relations
  assert rels[-1] == sorted(md['hop_relations'][0])
  assert all(md['relations'][n][1] == 'paper' for n in rels[-1])
  one_cell.make_state(one_cell.make_model(), 5)   # raises on a mismatch


def test_the_layer_bounds_are_the_harnesss_own_and_by_hand(one_cell):
  """``layer_bounds`` works the typed layout out from caps and fan-out
  alone: two relations from ``a`` at batch 4, fan-out [3, 2], caps that
  clamp hop 0 of ``a->b`` to 5 new rows. The cell refuses a program whose
  plan differs (a trim plan the reference mirrored would pass unseen)."""
  ab, ba = ('a', 'to', 'b'), ('b', 'back', 'a')
  rows, slots = hetero_node.layer_bounds(
      [ab, ba], {ab: [5, 100], ba: [100, 7]}, [3, 2], 'a', 4)
  # hop 0: a->b draws 4*3 = 12 slots, 5 new b; b->a has no frontier.
  # hop 1: b->a draws 5*2 = 10 slots, 7 new a; a has no NEW frontier (the
  # seeds were hop 0's), so a->b draws nothing
  assert rows == {'a': [4, 4, 11], 'b': [0, 5, 5]}
  assert slots == {ab: [0, 12, 12], ba: [0, 0, 10]}
  assert one_cell.row_bounds == {t: list(o) for t, o in
                                 one_cell.node_offsets.items()}
  import copy
  cfg = copy.deepcopy(one_cell.cfg)
  real = hetero_node.layer_bounds
  try:
    hetero_node.layer_bounds = lambda *a: (
        lambda r, s: ({t: [v[0]] + [x + 8 for x in v[1:]]
                       for t, v in r.items()}, s))(*real(*a))
    with pytest.raises(RuntimeError, match='lays the batch out'):
      hetero_node.Cell(cfg, one_cell.traffic, lambda k, v: None)
  finally:
    hetero_node.layer_bounds = real


def test_the_reference_gets_the_device_to_itself(one_cell):
  """``follower`` releases the program's device tables (the plain step at
  the real size does not fit beside 6.7 GB of them; PERF.md section 6): no
  live device array keeps a row table's shape, and the next trainer gets
  its tables placed again."""
  import gc

  import jax
  shapes = {tuple(f.device_table()[0].shape)
            for f in one_cell.dataset.node_features.values()}
  live = lambda: {tuple(a.shape) for a in jax.live_arrays()}
  assert shapes <= live()
  one_cell.follower(None, [])
  gc.collect()
  assert not shapes & live()
  assert shapes <= {tuple(f.device_table()[0].shape)
                    for f in one_cell.dataset.node_features.values()}


# ------------------------------ (c) the family through the drivers, as is

def _exact(out):
  return {k: v for k, v in out['compared'].items()
          if k not in control.MEASURED}


def test_the_typed_family_runs_through_the_same_driver(shared_cell, capsys):
  said = {}
  out = rehearse(capsys, CELL, trace=1, fixtures=TYPED, said=said)
  assert list(out)[-1] == 'compared' and out['correct'] is True
  assert out['attempted'] > 0 and out['failed'] == 0
  exact = _exact(out)
  cell = shared_cell
  assert set(exact) == (
      {f'{k}.{hetero_node.name_of(et)}' for et in cell.etypes
       for k in ('bad_edges', 'fanout_misses')} |
      {f'{k}.{t}' for t in cell.ntypes for k in ('dup_nodes', 'bad_rows')} |
      {'overflow'})
  assert all(v == {'value': 0, 'limit': 0} for v in exact.values())
  assert set(control.MEASURED) <= set(out['compared'])
  # the counts the readers get are sums over types; the split is said
  counts, split = said['valid_counts'], said['typed_counts']
  assert counts['nodes'][0] == cell.batch
  assert counts['nodes'] == pytest.approx(
      np.sum(list(split['nodes_by_type'].values()), 0))
  assert counts['edges'] == pytest.approx(
      np.sum(list(split['edges_by_relation'].values()), 0))
  assert counts['buffer_rows'] == sum(split['buffer_rows_by_type'].values())
  assert cell.step_flops(counts['nodes'], counts['edges']) > 0
  assert cell.collate_bytes() == 2 * sum(counts['nodes']) * 16 * 2


def _wrong_topic_edge(monkeypatch):
  """One wrong neighbour in ONE edge type: the first drawn neighbour of
  every ``paper -topic-> fos`` hop is moved to the next fos id."""
  import jax.numpy as jnp

  from graphlearn_tpu import ops
  real = ops.uniform_sample
  _, _, cfg, _, _ = run.load_cell(CELL, TYPED['bench_file'])
  d = cfg['dataset']
  e_topic = d['relations']['paper__topic__fos']['edges']
  n_paper, n_fos = d['node_types']['paper'], d['node_types']['fos']

  def broken(indptr, indices, seeds, seed_mask, k, key, meta=None):
    nbrs, epos, m = real(indptr, indices, seeds, seed_mask, k, key,
                         meta=meta)
    if indices.shape[0] == e_topic and indptr.shape[0] == n_paper + 1:
      nbrs = nbrs.at[0, 0].set(
          jnp.where(m[0, 0], (nbrs[0, 0] + 1) % n_fos, nbrs[0, 0]))
    return nbrs, epos, m

  monkeypatch.setattr(ops, 'uniform_sample', broken)


@pytest.mark.parametrize('fault,caught_by', [
    (_unchanged_state, 'dparam_gap'), (_half_batch, 'moment_gap'),
    (_wrong_topic_edge, 'bad_edges.paper__topic__fos')])
def test_a_broken_typed_path_comes_out_not_correct(
    fault, caught_by, shared_cell, monkeypatch, capsys):
  fault(monkeypatch)
  out = rehearse(capsys, CELL, seed=77, fixtures=TYPED)
  row = out['compared'][caught_by]
  assert out['correct'] is False and row['value'] > row['limit']
  if caught_by.startswith('bad_edges'):
    # the fault is named by its edge type, and by no other
    others = [k for k in out['compared']
              if k.startswith('bad_edges.') and k != caught_by]
    assert others and all(out['compared'][k]['value'] == 0 for k in others)


def test_control_reads_the_typed_cells_limits(shared_cell, capsys):
  readings = control.main(['--workload', CELL, '--seeds', '1',
                           '--control-seeds', '1', '--program-control', '1'],
                          **TYPED)
  capsys.readouterr()
  limits = run.load_cell(CELL, TYPED['bench_file'])[-1]
  by = {r['kind']: r for r in readings}
  passes = lambda r: all(r[k] <= limits[k] for k in control.MEASURED)
  assert passes(by['program'])
  assert not passes(by['control_ref_bf16'])
  assert not passes(by['control_program_bf16'])
  assert not passes(by['fault_half_batch'])
  assert not passes(by['fault_state_unchanged'])
  assert all(v == 0 for k, v in by['program'].items()
             if k not in control.MEASURED + ('kind', 'seed'))


# ------------------------------------------- the counts, by hand arithmetic

def test_typed_flops_and_bytes_by_hand():
  model = dict(
      kind='rgat', in_dim=8, hidden=4, heads=2, out_dim=3, layers=2,
      out_ntype='p', ntypes=['a', 'p'],
      relations={'p__c__p': ('p', 'p'), 'p__w__a': ('a', 'p'),
                 'a__rw__p': ('p', 'a')},
      hop_relations=[['p__c__p', 'p__w__a'],
                     ['a__rw__p', 'p__c__p', 'p__w__a']])
  assert reference.layer_relations(model) == [
      ['a__rw__p', 'p__c__p', 'p__w__a'], ['p__c__p', 'p__w__a']]
  nodes = {'p': [2, 5, 9], 'a': [0, 3, 4]}
  edges = {'p__c__p': [6, 20], 'p__w__a': [4, 11], 'a__rw__p': [0, 7]}
  from perfbench import flops
  gat = flops.gat_layer_flops
  want = (2 * 2 * 16 * 8 * 4 + 2 * 2 * 7 * 8 * 4 +      # the input Linears
          # layer 0 (2 hops): p->a reads p within 2 hops + a within 1;
          # p->p reads p within 2 hops once; a->p reads a within 2 + p in 1
          gat(16 + 3, 3, 7, 4, 2, 2, False) +
          gat(16, 7, 26, 4, 2, 2, False) +
          gat(7 + 7, 7, 15, 4, 2, 2, False) +
          # layer 1 (1 hop)
          gat(7, 2, 6, 4, 2, 2, False) + gat(3 + 2, 2, 4, 4, 2, 2, False) +
          3 * 2 * 2 * 4 * 3)                             # the classifier
  assert flops_hetero_node.step_flops(model, nodes, edges) == want
  assert flops_hetero_node.collate_bytes({'p': 16, 'a': 7}, 8, 2) == \
      2 * 23 * 8 * 2


# ------------------------- (d) the typed readers on a recorded v5e trace

class _TypedCell:
  ntypes = ['author', 'fos', 'institute', 'paper']

  def __init__(self, need):
    self._need = need

  def collate_bytes(self):
    return self._need


def test_typed_scope_names_parse():
  ts = typed_reduce.typed_scope
  nt = _TypedCell.ntypes
  assert ts(('glt.sample', 'hop1', 'paper__cites__paper', 'draw',
             'jit(uniform_sample)', 'gather'), nt) == (
                 'relation', 'hop1/paper__cites__paper/draw')
  assert ts(('glt.sample', 'hop2', 'merge', 'sort'), nt) == ('merge', 'hop2')
  assert ts(('glt.collate', 'paper', 'jit(gather_rows)', 'gather'), nt) == (
      'ntype', 'paper')
  # the homogeneous scopes, and ops directly under a layer, are not typed
  assert ts(('glt.sample', 'hop1', 'draw', 'gather'), nt) is None
  assert ts(('glt.collate', 'jit(gather_rows)', 'gather'), nt) is None
  assert ts(('glt.train', 'fwd_bwd', 'dot_general'), nt) is None
  assert ts((), nt) is None


def test_typed_readers_on_a_recorded_v5e_typed_chunk(capsys):
  from perfbench import trace_reduce
  with open(os.path.join(FIX, 'trace_v5e_typed_cut.expected.json')) as f:
    want = json.load(f)
  device, host = trace_reduce.load(
      os.path.join(FIX, 'trace_v5e_typed_cut.json'))
  busy_s, window_s, _ = trace_reduce.busy(device)
  slice_ = dict(device=device, host=host, steps=want['steps'],
                busy_s=busy_s, window_s=window_s)
  run_ = dict(cell=_TypedCell(want['collate_bytes']), traffic={},
              window=dict(steps=0, wall_s=0.0),
              peaks=dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12),
              counts=dict(nodes=want['nodes'], edges=[], buffer_rows=1),
              scan=slice_)
  got = {name: importlib.import_module(
      f'perfbench.layer_metrics.{name}').read(run_) for name in READERS}
  for name in READERS:
    assert got[name] == pytest.approx(want['readers'][name], rel=1e-9), name
  # the four layer times are the chunk program's busy time
  four = sum(got[n] for n in READERS[:4])
  assert four == pytest.approx(
      1e3 * want['chunk_busy_seconds'] / want['steps'], rel=1e-9)
  assert 0 < got['typed_collate_roofline'] < 100
  split = run_['typed_reduce']
  assert set(split) == {'relation', 'merge', 'ntype'}
  assert set(split['ntype']) == set(want['ntype_ms'])
  for k, v in want['ntype_ms'].items():
    assert split['ntype'][k] == pytest.approx(v, rel=1e-9)
  for k, v in want['relation_ms'].items():
    assert split['relation'][k] == pytest.approx(v, rel=1e-9)
  # the split stays inside its layers
  assert sum(split['ntype'].values()) <= got['typed_collate_ms'] * (1 + 1e-9)
  assert (sum(split['relation'].values()) + sum(split['merge'].values())
          <= got['typed_sample_ms'] * (1 + 1e-9))
  # the draws are the split's, the tiles are counted draw by draw (six of
  # the sixteen typed draws are wide enough to tile), the host's gaps are
  # what the products cells' reader reads
  assert got['typed_draw_ms'] == pytest.approx(sum(
      v for k, v in split['relation'].items() if k.endswith('/draw')))
  assert run_['typed_tiles'] == want['tiles_per_step']
  assert got['typed_draw_tiles_per_step'] == sum(
      want['tiles_per_step'].values())
  from perfbench.layer_metrics import host_gap_ms
  assert got['typed_host_gap_ms'] == host_gap_ms.read(run_) > 0
  said = [json.loads(l[len('perfbench: '):])
          for l in capsys.readouterr().out.splitlines()
          if l.startswith('perfbench: {"typed_')]
  assert len(said) == 2      # each reduced once, said once
  # a homogeneous slice has no typed scope: nothing is said, nothing read
  homo, hhost = trace_reduce.load(os.path.join(FIX,
                                               'trace_v5e_scan_cut.json'))
  b, w, _ = trace_reduce.busy(homo)
  plain = dict(cell=_TypedCell(1), scan=dict(device=homo, host=hhost,
                                             steps=1, busy_s=b, window_s=w))
  assert typed_reduce.split(plain) is None
  assert typed_reduce.tiles(plain) is None
