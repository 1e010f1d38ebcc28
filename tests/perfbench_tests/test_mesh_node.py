"""Family ``mesh_node`` (PR 35): the partitioned graph's ``Cell``, its
generator, its plain reference, its executor and its eight readers — added
as files, run through ``run.main`` and ``control.main`` as they stand, on
4 of the 8 virtual CPU devices.

Like the other files here these test the yardstick: the generator's
rows and labels regenerated bit for bit by ``numpy`` and by ``jax.numpy``,
every shard's CSR on its own device under the partition rule, the
program's ``GraphSAGE`` + ``pmean`` against the reference's mean of shard
gradients (and that the program's bfloat16 path falls outside the same
tolerances), the whole command at a toy size with ``correct`` true and
false under a wrong remote row, a dropped remote neighbour, half a batch
and an unchanged state, the FLOP and byte counts by hand, and the
readers on a hand-cut recorded 4-chip v5e trace.
"""
import json
import os

import numpy as np
import pytest

from perfbench import (control, datagen_mesh_node as datagen,
                       flops_mesh_node, mesh_reduce, run)
from perfbench import reference_mesh_node as reference
from perfbench.families import mesh_node
from test_perfbench import (TINY, _half_batch, rehearse,
                            test_benchmark_json_names_only_files_that_exist
                            as names_only_files_that_exist)

MESH = dict(TINY, bench_file='perfbench/fixtures/BENCHMARK.mesh.json')
CELL = 'tiny-papers.tiny-mesh-scan'
FIX = os.path.join(run.ROOT, 'perfbench', 'fixtures')
READERS = ['mesh_sample_ms', 'mesh_collate_ms', 'mesh_train_ms',
           'mesh_unscoped_ms', 'mesh_exchange_ms', 'mesh_busy_skew',
           'mesh_cache_hit_share', 'mesh_collate_roofline']


@pytest.fixture(scope='module')
def one_cell():
  """The toy partitioned dataset, built once: every run of this file sees
  the same graph, rows and caps, as every seed of a cell does."""
  _, _, cfg, traffic, _ = run.load_cell(CELL, MESH['bench_file'])
  return mesh_node.Cell(cfg, traffic, lambda k, v: None)


@pytest.fixture
def shared_cell(one_cell, monkeypatch):
  monkeypatch.setattr(mesh_node, 'Cell', lambda cfg, traffic, log: one_cell)
  return one_cell


def test_the_files_the_fixture_and_the_cell_name_exist():
  names_only_files_that_exist(MESH['bench_file'])
  with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  cell = {w['name']: w for w in bench['workloads']}['sage-papers.mesh-exact']
  assert cell['chips'] == 4
  assert sum(w['chips'] == 4 for w in bench['workloads']) == 1
  mine = [m for m in bench['per_layer'] if m['name'].startswith('mesh_')]
  assert [m['name'] for m in mine] == READERS
  assert all(m['workloads'] == [cell['name']] for m in mine)
  _, _, cfg, traffic, limits = run.load_cell(cell['name'], 'BENCHMARK.json')
  assert set(mesh_node.EXACT) <= set(limits)
  assert traffic['reference_steps'] == traffic['chunk_size'] == 16
  assert cfg['steps_per_call'] % traffic['chunk_size'] == 0
  d, pub = cfg['dataset'], cfg['published']
  # a third of the published shape, the mean degree kept; widths whole
  assert d['num_nodes'] == pub['num_nodes'] // 3
  assert abs(d['num_directed_edges'] / d['num_nodes'] -
             pub['num_directed_edges'] / pub['num_nodes']) < 1e-6
  assert (d['feat_dim'], d['num_classes']) == (128, 172)
  assert cfg['feature_store'] == dict(
      split_ratio=0.05, hotness='in_degree', bucket_frac=2.0,
      wire_dtype='float32', miss_dedup=True, seed_labels_only=True)


def test_the_reference_imports_nothing_of_the_program():
  with open(reference.__file__) as f:
    source = f.read()
  assert 'graphlearn_tpu' not in source and 'from perfbench' not in source
  assert 'shard_map' not in source.split('"""')[2]
  assert 'psum' not in source and 'pmean' not in source.split('"""')[2]


# ------------------------------------------------------- (a) the generator

@pytest.mark.parametrize('ids', [
    np.arange(0, 4001), np.array([0, 1, 37_019_984, 2 ** 31 - 1, 7, 7])])
def test_rows_and_labels_are_the_same_bits_from_numpy_and_jax(ids):
  import jax.numpy as jnp
  centre = datagen.centres(11, 172, 128, 0.1)
  host = datagen.rows_of(np, ids, 11, 172, centre)
  dev = np.asarray(datagen.rows_of(jnp, jnp.asarray(ids, jnp.int32), 11, 172,
                                   jnp.asarray(centre)))
  assert host.dtype == np.float32 and host.shape == (ids.size, 128)
  assert host.tobytes() == dev.tobytes()
  assert np.array_equal(
      datagen.labels_of(np, ids, 11, 172),
      np.asarray(datagen.labels_of(jnp, jnp.asarray(ids, jnp.int32), 11,
                                   172)))
  # the law: noise uniform on [-2, 2) plus a centre of snr times as much
  assert np.abs(host).max() < 2 * 1.1 + 1e-6
  if ids.size > 1000:
    assert abs(host.var() - 4 / 3) < 0.05
    assert datagen.rows_of(np, ids, 12, 172, centre).tobytes() != \
        host.tobytes()


def test_every_shard_is_generated_on_its_own_device_under_the_rule(one_cell):
  cell = one_cell
  d = cell.cfg['dataset']
  n, e, parts = d['num_nodes'], d['num_directed_edges'], cell.parts
  ga = cell.dataset.graph.device_arrays(cell.mesh)
  fa = cell.dataset.node_features.device_arrays()
  devs = list(cell.mesh.devices.flat)
  for a in (ga['row_ids'], ga['indptr'], ga['indices'], fa['feats']):
    assert [s.device for s in sorted(
        a.addressable_shards, key=lambda s: s.index[0].start)] == devs
  row_ids, indptr, indices = (np.asarray(ga[k]) for k in (
      'row_ids', 'indptr', 'indices'))
  total, indeg = 0, np.zeros(n, np.int64)
  for p in range(parts):
    rows = -(-(n - p) // parts)
    assert np.array_equal(row_ids[p, :rows], p + parts * np.arange(rows))
    assert (row_ids[p, rows:] == np.iinfo(np.int32).max).all()
    assert (np.diff(indptr[p]) >= 0).all()
    mine = int(indptr[p, -1])
    assert mine == e // parts + (p < e % parts)
    total += mine
    cols = indices[p, :mine]
    assert cols.min() >= 0 and cols.max() < n
    assert (indices[p, mine:] == -1).all()
    indeg += np.bincount(cols, minlength=n)
  assert total == e
  # the hotness that ranks the cache is the in-degree of the whole graph
  want = np.sort(np.argsort(-indeg, kind='stable')[:int(n * 0.05)])
  assert np.array_equal(cell.dataset.node_features.cache_ids, want)
  # rows, labels and the cache: the generator's law, by id
  fid = np.asarray(fa['feat_ids'])
  feats = np.asarray(fa['feats'])
  for p in range(parts):
    own = fid[p][fid[p] < n]
    assert feats[p, :own.size].tobytes() == cell.rows(own).tobytes()
    assert not feats[p, own.size:].any()
  assert np.asarray(fa['cache_feats']).tobytes() == cell.rows(want).tobytes()
  lab = np.asarray(cell.dataset.node_labels.device_arrays()['feats'])
  assert np.array_equal(lab[0, :5, 0], cell.labels(fid[0, :5]))
  assert np.unique(cell.train_idx).size == d['num_train']
  # communities show in the edges: p_intra of the targets share a label
  src = np.repeat(row_ids[0, :-(-(n) // parts)], np.diff(indptr[0])[
      :-(-(n) // parts)])
  same = cell.labels(src) == cell.labels(indices[0, :src.size])
  assert 0.5 < same.mean() < 0.8


# ------------------- (b) GraphSAGE + pmean against the plain reference

def _program_run(cell, seed, steps, dtype=None):
  """The program's per-step mesh loop (``DistNeighborLoader`` batches,
  ``DistFusedEpochTrainer.train_step``: per-shard gradients, ``pmean``,
  one update): per-step losses, params and first moment after ``steps``,
  and the batches as the replay hands them over."""
  import jax

  import graphlearn_tpu as glt
  model = cell.make_model(dtype)
  state, tx, params0 = cell.make_state(model, seed)
  loader = cell.make_loader(seed)
  tr = glt.loader.DistFusedEpochTrainer(loader, model, tx, cell.num_classes)
  losses, batches = [], []
  for _, b in zip(range(steps), loader):
    batches.append(jax.device_get(dict(
        node=b.node, edge_index=b.edge_index, edge_mask=b.edge_mask,
        num_sampled_nodes=b.num_sampled_nodes, x=b.x, y=b.y,
        overflow=b.metadata['overflow'])))
    state, loss, _ = tr.train_step(state, b)
    losses.append(float(loss))
  return (np.array(losses), jax.device_get(state.params),
          jax.device_get(state.opt_state[0].mu), params0, batches)


def test_graphsage_with_pmean_matches_the_mean_of_shard_gradients(one_cell):
  """``GraphSAGE(merge_dense)`` on P shard batches under ``pmean`` and the
  plain reference's mean of P shard gradients, from the same seeded
  weights over the same three steps. Tolerances, each with its reason
  (float32 on XLA:CPU, where both multiply exactly): loss 2e-6 relative
  (two summation orders of one float32 forward, and the mean of four
  losses taken in another order); the parameters' change after 3 steps
  1e-3 and the first moment 1e-3 by ``check.py``'s measure (the k-run
  mean against segment sums, re-ordered once more in the backward pass
  and once across shards). The program's own bfloat16 path must fall
  outside: its loss misses by 1e-4 and its moment by 1e-3."""
  import jax
  import jax.numpy as jnp

  from perfbench import check
  cell, seed, steps = one_cell, 4321, 3
  losses, params, mu, params0, batches = _program_run(cell, seed, steps)
  assert cell.exact_numbers(batches, steps) == dict.fromkeys(
      mesh_node.EXACT, 0)
  rl, rg0, rparams, rmu = cell.follower(params0, batches)()
  assert (np.abs(losses - rl) / np.abs(rl)).max() < 2e-6
  dp = jax.tree.map(lambda a, b: a - b, params, params0)
  rdp = jax.tree.map(lambda a, b: a - b, rparams, params0)
  gap = check._worst_leaf_gap(check._leaves(dp), check._leaves(rdp))
  assert gap < 1e-3
  mgap = check._worst_leaf_gap(check._leaves(mu), check._leaves(rmu))
  assert mgap < 1e-3
  bl, _, bmu, _, _ = _program_run(cell, seed, steps, dtype=jnp.bfloat16)
  assert (np.abs(bl - rl) / np.abs(rl)).max() > 1e-4
  assert check._worst_leaf_gap(check._leaves(bmu),
                               check._leaves(rmu)) > 1e-3


# ----------------------------------- (c) the whole command, and its faults

def test_the_command_rehearses_the_mesh_cell_on_virtual_devices(
    shared_cell, capsys):
  said = {}
  out = rehearse(capsys, CELL, fixtures=MESH, said=said)
  assert out['correct'] is True and out['failed'] == 0
  assert set(out['compared']) == set(mesh_node.EXACT) | set(control.MEASURED)
  assert all(out['compared'][k]['value'] == 0 for k in mesh_node.EXACT)
  assert out['attempted'] % shared_cell.steps_per_call == 0
  win = said['window']
  # a step trains P x batch seeds; the program's own counters moved
  assert win['seeds'] == win['steps'] * 4 * shared_cell.batch
  c = win['counters']
  assert c['dist_feature.lookups'] > c['dist_feature.hits'] > 0
  assert c['dist_exchange.rows.hop0'] > 0
  assert said['partitions'] == 4 and said['cache_rows'] == 200


def _wrong_remote_row(monkeypatch):
  """One row that came from another shard arrives changed."""
  import jax
  import jax.numpy as jnp

  from graphlearn_tpu.distributed import DistFeature
  real = DistFeature._shard_body

  def broken(self, b, slab=False):
    body = real(self, b, slab)

    def wrapped(feat_ids, feats, pb, cache_ids, cache_feats, stats, ids,
                mask):
      out, st = body(feat_ids, feats, pb, cache_ids, cache_feats, stats,
                     ids, mask)
      if not jnp.issubdtype(out.dtype, jnp.floating):
        return out, st
      remote = mask & (pb[jnp.maximum(ids, 0)] != jax.lax.axis_index('g'))
      hit = jnp.isin(ids, cache_ids)
      victim = jnp.argmax(remote & ~hit)
      return out.at[victim, 0].add(0.25), st

    return wrapped

  monkeypatch.setattr(DistFeature, '_shard_body', broken)


def _dropped_remote_neighbour(monkeypatch):
  """The exchange loses one neighbour of a frontier node another shard
  expanded."""
  import jax
  import jax.numpy as jnp

  from graphlearn_tpu.distributed import dist_neighbor_sampler as dns
  real = dns._exchange_hop

  def broken(garr, pb, frontier, fmask, *a, **kw):
    nbrs, m, e = real(garr, pb, frontier, fmask, *a, **kw)
    remote = fmask & m[:, 0] & (
        pb[jnp.maximum(frontier, 0)] != jax.lax.axis_index('g'))
    victim = jnp.argmax(remote)
    return nbrs, m.at[victim, 0].set(m[victim, 0] & ~remote.any()), e

  monkeypatch.setattr(dns, '_exchange_hop', broken)


def _unchanged_state(monkeypatch):
  from graphlearn_tpu.loader.pipeline import DistFusedEpochTrainer
  real = DistFusedEpochTrainer._dp_step_body

  def stuck(self, state, batch):
    _, loss, acc = real(self, state, batch)
    return state, loss, acc

  monkeypatch.setattr(DistFusedEpochTrainer, '_dp_step_body', stuck)


@pytest.mark.parametrize('fault, caught_by', [
    (_wrong_remote_row, 'bad_rows'),
    (_dropped_remote_neighbour, 'fanout_misses'),
    (_half_batch, 'loss_gap_step1'), (_unchanged_state, 'dparam_gap')])
def test_a_broken_mesh_path_comes_out_not_correct(fault, caught_by,
                                                  shared_cell, monkeypatch,
                                                  capsys):
  fault(monkeypatch)
  # the shared dataset's store keeps its compiled lookups: a fault
  # planted in the lookup has to be traced anew
  monkeypatch.setattr(shared_cell.dataset.node_features, '_fns', {})
  out = rehearse(capsys, CELL, seed=77, fixtures=MESH)
  row = out['compared'][caught_by]
  assert out['correct'] is False and row['value'] > row['limit']


def test_the_bfloat16_control_fails_and_the_program_passes(shared_cell,
                                                           capsys):
  readings = control.main(
      ['--workload', CELL, '--seeds', '1', '--control-seeds', '1',
       '--program-control', '1'], **MESH)
  capsys.readouterr()
  limits = run.load_cell(CELL, MESH['bench_file'])[-1]
  by = {r['kind']: r for r in readings}
  passes = lambda r: all(r[k] <= limits[k] for k in control.MEASURED)
  assert passes(by['program'])
  assert all(by['program'][k] == 0 for k in mesh_node.EXACT)
  assert by['control_ref_bf16']['loss_gap_step1'] > limits['loss_gap_step1']
  assert not passes(by['control_program_bf16'])
  assert not passes(by['fault_half_batch'])
  assert not passes(by['fault_state_unchanged'])


# ------------------------------------------- (d) counts by hand, one chip

def test_flops_and_exchange_bytes_by_hand():
  """One shard batch of a 2-layer mean-SAGE 8 -> 16 -> 5 with 10 seeds, 30
  hop-1 rows, 50 hop-2 rows; 40 and 90 valid edges. Layer 0 makes rows
  for seeds + hop 1 (40 rows) from both hops' edges (130): forward+backward
  2 matmuls x 2 x 40 x 8 x 16 x 2 (no input gradient at layer 0) + the
  mean's 130 x 8 x 2. Layer 1 makes the 10 seed rows from hop 1's 40
  edges: 2 x 2 x 10 x 16 x 5 x 3 + 40 x 16 x 2."""
  model = dict(kind='sage', in_dim=8, hidden=16, out_dim=5, layers=2)
  want = (2 * 2 * 40 * 8 * 16 * 2 + 130 * 8 * 2) + (
      2 * 2 * 10 * 16 * 5 * 3 + 40 * 16 * 2)
  assert flops_mesh_node.step_flops(model, [10, 30, 50], [40, 90]) == want
  assert flops_mesh_node.collate_bytes(90, 128, 4) == 2 * 90 * 512
  # 100 frontier ids sent away at fan-out 5: 100 ids out, 500 ids + 500
  # mask bytes back
  assert flops_mesh_node.hop_exchange_bytes(100, 5) == 100 * (4 + 5 * 5)
  # 400 unique missed rows at P = 4: 300 on other chips, id out + row back
  assert flops_mesh_node.row_exchange_bytes(400, 4, 128) == 300 * (4 + 512)
  assert flops_mesh_node.allreduce_bytes(1000, 4) == 2 * 0.75 * 4000


def test_valid_counts_are_one_chips_share(one_cell):
  """``valid_counts`` is the mean over SHARD batches, so ``step_mfu`` and
  ``pad_share`` read one chip."""
  cell = one_cell
  eo = cell.edge_offsets
  nsn = np.array([[[16], [10], [20]], [[16], [30], [40]],
                  [[16], [20], [30]], [[16], [20], [30]]])
  em = np.zeros((4, eo[-1]), bool)
  em[:, :8] = True
  em[0, eo[0]:eo[0] + 4] = True
  got = cell.valid_counts([dict(num_sampled_nodes=nsn, edge_mask=em)])
  assert got['nodes'] == [16.0, 20.0, 30.0]
  assert got['edges'] == [8.0, 1.0]
  assert got['buffer_rows'] == cell.node_offsets[-1]


# ------------------------- (e) the readers on a recorded 4-chip v5e trace

class _Counts:
  """What the readers ask of a cell, for a recorded trace."""

  def collate_bytes(self, nodes):
    return flops_mesh_node.collate_bytes(sum(nodes), 128, 4)

  def exchange_bytes(self, counters, steps):
    return {}


def _run_from_fixture():
  from perfbench import trace_reduce
  with open(os.path.join(FIX, 'trace_v5e_mesh_cut.expected.json')) as f:
    want = json.load(f)
  device, host = trace_reduce.load(os.path.join(FIX,
                                                'trace_v5e_mesh_cut.json'))
  busy_s, window_s, gaps = trace_reduce.busy(device,
                                             trace_reduce.window_of(host))
  run_ = dict(cell=_Counts(), traffic={}, counts=want['counts'],
              peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
              window=dict(steps=48, wall_s=12.12,
                          counters=want['window_counters']),
              scan=dict(device=device, host=host, steps=want['steps'],
                        busy_s=busy_s, window_s=window_s, gaps=gaps))
  return run_, want


def test_the_eight_readers_on_a_recorded_four_chip_trace(capsys):
  """The first scanned step of ``sage-papers.mesh-exact`` on each chip of
  a v5e 2x2 (hand-cut, PR 35): self time per layer per chip, the
  collectives under their own scopes, every chip's busy time."""
  import importlib
  run_, want = _run_from_fixture()
  assert {e['chip'] for e in run_['scan']['device']} == {
      f'/device:TPU:{i}' for i in range(4)}
  r = mesh_reduce.chips(run_)
  assert r['programs'] == want['programs']        # found by what runs in it
  assert set(r['layers']) == set(r['busy_ms']) == set(want['busy_ms_by_chip'])
  for chip, ms in want['busy_ms_by_chip'].items():
    assert r['busy_ms'][chip] == pytest.approx(ms, rel=1e-9)
  for layer, ms in want['layers_chip0'].items():
    assert r['layers']['/device:TPU:0'][layer] == pytest.approx(ms, rel=1e-9)
  # the collectives sit under the exchange scopes and nowhere else; a
  # bucket that may overflow runs under lax.cond, dropped from the name
  assert set(want['exchange_chip0']) == {'glt.collate/exchange',
                                         'glt.train/allreduce'}
  assert r['exchange']['/device:TPU:0'] == pytest.approx(
      want['exchange_chip0'], rel=1e-9)
  assert {'glt.sample/hop2/exchange', 'glt.sample/hop2/draw',
          'glt.collate/cache', 'glt.collate/exchange',
          'glt.train/allreduce'} <= set(r['sub_scopes'])
  got = {n: importlib.import_module(f'perfbench.layer_metrics.{n}').read(run_)
         for n in READERS}
  for name, value in want['metrics'].items():
    assert got[name] == pytest.approx(value, rel=1e-9), name
  # the four layers add up to the chunk program's busy time on a chip:
  # the slice's busy time less the seed program in front of the chunk
  four = sum(got[n] for n in READERS[:4])
  seeds_ms = 1e-3 * max(e['dur'] for e in run_['scan']['device']
                        if e['name'].startswith('jit_epoch_seeds'))
  assert four == pytest.approx(1e3 * run_['scan']['busy_s'] - seeds_ms,
                               rel=2e-3)
  assert 0 <= got['mesh_busy_skew'] < 0.01
  assert 0 < got['mesh_collate_roofline'] < 100
  assert got['mesh_exchange_ms'] < 0.01 * four    # the wire is not the cost
  lines = [json.loads(l[len('perfbench: '):])
           for l in capsys.readouterr().out.splitlines()
           if l.startswith('perfbench: ')]
  assert len(lines) == 1 and 'mesh_reduce' in lines[0]   # printed once
  line = lines[0]['mesh_reduce']
  assert line['sum_of_layers'] == pytest.approx(four, rel=1e-9)
  assert line['glt.collate']['max'] >= line['glt.collate']['mean']


def test_the_mesh_readers_find_nothing_on_a_one_chip_trace_without_scopes():
  """A program from before PR 35 names no exchange scope, and a trace of
  another cell holds no mesh chunk: every reader returns None, never 0."""
  import importlib

  from perfbench import trace_reduce
  device, host = trace_reduce.load(os.path.join(FIX, 'trace_v5e_cut.json'))
  busy_s, window_s, gaps = trace_reduce.busy(device,
                                             trace_reduce.window_of(host))
  run_ = dict(cell=_Counts(), traffic={}, counts=dict(nodes=[]),
              peaks=dict(hbm_bytes_per_s=819e9), window=dict(steps=0),
              scan=dict(device=device, host=host, steps=2, busy_s=busy_s,
                        window_s=window_s, gaps=gaps))
  for n in READERS:
    assert importlib.import_module(
        f'perfbench.layer_metrics.{n}').read(run_) is None, n


def test_exchange_scope_and_collective_names():
  assert mesh_reduce.exchange_scope(
      ('glt.sample', 'cond', 'branch_1_fun', 'hop1', 'exchange',
       'all_to_all')) == 'glt.sample/hop1/exchange'
  assert mesh_reduce.exchange_scope(
      ('glt.train', 'allreduce', 'psum')) == 'glt.train/allreduce'
  assert mesh_reduce.exchange_scope(('glt.sample', 'hop1', 'draw')) is None
  ev = lambda name: dict(name=name)
  assert mesh_reduce.is_collective(ev('all-to-all.12'))
  assert mesh_reduce.is_collective(ev('all-reduce-start.3'))
  assert mesh_reduce.is_collective(ev('all-reduce-done'))
  assert not mesh_reduce.is_collective(ev('fusion.7'))
  assert mesh_reduce.sub_scope(('glt.collate', 'cache', 'gather')) == \
      'glt.collate/cache'
  assert mesh_reduce.sub_scope(()) == 'unscoped'
