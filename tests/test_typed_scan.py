"""Typed graphs through the LOCAL scanned epoch (PR 30, ROADMAP M1).

``ScanTrainer`` over a typed ``NeighborLoader`` (seeds of one node type)
traces the sampler's own typed hop loop and the per-type collate into the
same chunk program the homogeneous epoch runs. Held here: the scanned
typed epoch equals the per-batch typed loader + ``make_train_step`` loop
under the same keys (batches bit for bit, losses and params as
``test_scan_trainer_matches_per_step_loop`` holds the homogeneous one), at
the homogeneous epoch's dispatch budget with zero steady compiles; what it
still refuses it refuses by name.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.models import RGNN, train as train_lib
from graphlearn_tpu.typing import reverse_edge_type

CITES = ('paper', 'cites', 'paper')
WRITTEN_BY = ('paper', 'written_by', 'author')
REV_WRITTEN_BY = ('author', 'rev_written_by', 'paper')
BATCH, FAN, CLASSES = 8, [3, 2], 5


def make_typed_dataset(n_paper=240, n_author=160, f=8, dtype=np.float16):
  """Two node types, three stored relations (one self relation, one
  bipartite relation both ways), half-precision rows, labels on paper."""
  rng = np.random.default_rng(0)
  cites = np.stack([rng.integers(0, n_paper, 1200),
                    rng.integers(0, n_paper, 1200)])
  wb = np.stack([rng.integers(0, n_paper, 700),
                 rng.integers(0, n_author, 700)])
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph({CITES: cites, WRITTEN_BY: wb,
                 REV_WRITTEN_BY: wb[::-1].copy()}, graph_mode='CPU',
                num_nodes={CITES: n_paper, WRITTEN_BY: n_paper,
                           REV_WRITTEN_BY: n_author})
  ds.init_node_features({
      'paper': rng.standard_normal((n_paper, f)).astype(dtype),
      'author': rng.standard_normal((n_author, f)).astype(dtype)})
  ds.init_node_labels({'paper': rng.integers(0, CLASSES, n_paper)})
  return ds


def typed_setup(conv, calibrated, num_seeds=44, **loader_kw):
  """(make_loader, model): a 44-seed pool at batch 8 gives 5 full steps +
  a ragged tail, so K=4 scans a full chunk and a tail chunk."""
  ds = make_typed_dataset()
  pool = np.random.default_rng(9).permutation(240)[:num_seeds]
  caps = None
  if calibrated:
    caps = glt.sampler.estimate_hetero_frontier_caps(
        ds.graph, FAN, {'paper': BATCH}, input_nodes={'paper': pool},
        num_probes=5, slack=1.5, seed=0, multiple=8)
  loader_kw.setdefault('shuffle', False)

  def make_loader():
    return glt.loader.NeighborLoader(
        ds, FAN, ('paper', pool), batch_size=BATCH, seed=0, dedup='merge',
        frontier_caps=caps, **loader_kw)

  recs, node_offs, edge_offs = glt.sampler.hetero_tree_blocks(
      {'paper': BATCH}, tuple(ds.graph), FAN, etype_caps=caps)
  model = RGNN(etypes=tuple(reverse_edge_type(et) for et in ds.graph),
               hidden_dim=16, out_dim=CLASSES, num_layers=len(FAN),
               out_ntype='paper', conv=conv, heads=4 if conv == 'gat' else 1,
               hop_node_offsets=node_offs, hop_edge_offsets=edge_offs,
               merge_dense=True, tree_records=recs)
  return make_loader, model


def _leaves_equal(a, b):
  import jax
  la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
  assert len(la) == len(lb)
  for x, y in zip(la, lb):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize('calibrated', [True, False])
@pytest.mark.parametrize('conv', ['sage', 'gat'])
def test_typed_scan_epoch_matches_per_batch_loop(conv, calibrated):
  """shuffle=False: the scanned typed epoch == the per-batch typed
  loader loop — every batch bit for bit (node buffers, edge lists, masks,
  half-precision rows, labels), identical per-step losses and final
  params, over a ragged tail batch and a tail chunk; and the sampler's
  host counter has advanced by the same count, so a second epoch of both
  still matches."""
  import jax
  make, model = typed_setup(conv, calibrated)
  first = train_lib.batch_to_dict(next(iter(make())))
  assert first['x']['paper'].dtype == np.float16

  ref_loader = make()
  state_ref, tx = train_lib.create_train_state(
      model, jax.random.PRNGKey(0), first, lr=1e-2)
  step, _ = train_lib.make_train_step(model, tx, CLASSES)
  batches, losses_ref = [], []
  for b in ref_loader:
    d = train_lib.batch_to_dict(b)
    batches.append(jax.device_get(dict(d, node=b.node)))
    state_ref, loss, _ = step(state_ref, d)
    losses_ref.append(np.asarray(loss))
  assert len(losses_ref) == 6

  scan_loader = make()
  state_scan, _ = train_lib.create_train_state(
      model, jax.random.PRNGKey(0), first, optimizer=tx)
  trainer = glt.loader.ScanTrainer(scan_loader, model, tx, CLASSES,
                                   chunk_size=4)
  # the chunk's own traced body, step by step under the epoch's keys
  seed_mat, mask_mat = trainer._seed_fn(
      jax.device_put(np.asarray(scan_loader.input_seeds, np.int32)),
      jax.random.fold_in(trainer._perm_key, 0), 6)
  body = jax.jit(lambda fargs, feats, id2i, labels, seeds, smask, count:
                 trainer._sample_collate(
                     fargs, feats, id2i, labels, seeds, smask,
                     trainer._step_keys(scan_loader.sampler._key, count)))
  for g, want in enumerate(batches):
    got, overflow = body(trainer._sample_args(), trainer._feats,
                         trainer._id2i, trainer._labels, seed_mat[g],
                         mask_mat[g], np.int32(1 + g * trainer._key_stride))
    assert not bool(overflow)
    want = dict(want)
    want.pop('node')
    _leaves_equal(got, want)

  state_scan, losses, accs = trainer.run_epoch(state_scan)
  assert np.asarray(losses).shape == (6,)
  np.testing.assert_array_equal(np.asarray(losses),
                                np.asarray(losses_ref).reshape(-1))
  _leaves_equal(state_scan.params, state_ref.params)
  assert scan_loader.sampler._call_count == ref_loader.sampler._call_count \
      == 6 * trainer._key_stride

  for b in ref_loader:
    state_ref, _, _ = step(state_ref, train_lib.batch_to_dict(b))
  state_scan, losses2, _ = trainer.run_epoch(state_scan)
  assert np.asarray(losses2).shape == (6,)
  _leaves_equal(state_scan.params, state_ref.params)


def test_typed_epoch_dispatch_and_retrace_budget():
  """A typed scanned epoch costs what a homogeneous one does:
  ``ceil(steps/K) + 2`` dispatches (``dispatches_per_step`` equal), one
  chunk executable per chunk length, zero steady compiles."""
  import jax

  from graphlearn_tpu.metrics import programs
  from test_scan_epoch import _make_loader, make_dataset
  from graphlearn_tpu.models import GraphSAGE
  steps, chunk = 6, 4
  make, model = typed_setup('gat', True, shuffle=True)
  first = train_lib.batch_to_dict(next(iter(make())))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  trainer = glt.loader.ScanTrainer(make(), model, tx, CLASSES,
                                   chunk_size=chunk)
  c0 = programs.compile_count('scan_chunk')
  state, _, _ = trainer.run_epoch(state)     # compile outside the count
  assert programs.compile_count('scan_chunk') - c0 == 2
  with programs.retrace_budget('scan_chunk', 0):
    with glt.utils.count_dispatches() as typed:
      state, losses, _ = trainer.run_epoch(state)
  assert len(losses) == steps
  assert typed.counts['scan_chunk'] == -(-steps // chunk)
  assert programs.compile_count('scan_chunk') - c0 == 2

  ds = make_dataset()
  homo_model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  hfirst = train_lib.batch_to_dict(next(iter(_make_loader(ds, 44))))
  hstate, htx = train_lib.create_train_state(
      homo_model, jax.random.PRNGKey(0), hfirst)
  homo = glt.loader.ScanTrainer(_make_loader(ds, 44), homo_model, htx, 3,
                                chunk_size=chunk)
  hstate, _, _ = homo.run_epoch(hstate)
  with glt.utils.count_dispatches() as plain:
    hstate, hlosses, _ = homo.run_epoch(hstate)
  assert len(hlosses) == steps
  assert typed.total == plain.total == -(-steps // chunk) + 2
  assert dict(typed.counts) == dict(plain.counts)


def test_typed_scan_refusals_keep_their_messages():
  """What the local scanned epoch does not take, it refuses by name:
  with_edge batches, seeds of no one node type, and the executor whose
  loop keeps the homogeneous key stream."""
  import optax

  from graphlearn_tpu.sampler import CapacityPlanError
  make, model = typed_setup('sage', False)
  tx = optax.adam(1e-3)
  ds = make().data
  with_edge = glt.loader.NeighborLoader(
      ds, FAN, ('paper', np.arange(16)), batch_size=BATCH, with_edge=True,
      dedup='merge')
  with pytest.raises(ValueError, match='with_edge batches are not'):
    glt.loader.ScanTrainer(with_edge, model, tx, CLASSES)
  with pytest.raises(CapacityPlanError, match='loader.ScanTrainer'):
    glt.loader.RunTrainer(make(), model, tx, CLASSES)
  # a typed loader is refused by name only where it is typed: the same
  # trainer class still builds over a homogeneous loader
  from test_scan_epoch import _make_loader, make_dataset
  from graphlearn_tpu.models import GraphSAGE
  homo = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  glt.loader.RunTrainer(_make_loader(make_dataset(), 16), homo, tx, 3)


def _typed_chunk_text():
  """The typed chunk's lowered program text (nothing runs)."""
  import jax
  import jax.numpy as jnp
  make, model = typed_setup('gat', True)
  loader = make()
  first = train_lib.batch_to_dict(next(iter(make())))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  tr = glt.loader.ScanTrainer(loader, model, tx, CLASSES, chunk_size=4)
  seed_mat, mask_mat = tr._seed_fn(
      jax.device_put(np.asarray(loader.input_seeds, np.int32)),
      jax.random.fold_in(tr._perm_key, 0), 6)
  return tr._chunk_fn.lower(
      state, jnp.zeros((), bool), tr._sample_args(), tr._feats, tr._id2i,
      tr._labels, seed_mat, mask_mat, loader.sampler._key, jnp.int32(1),
      jnp.int32(0), 4).as_text()


def test_typed_chunk_is_the_same_program_in_every_process():
  """The typed plan and the typed conv name their node types in sorted
  order, never in a set's: a set of strings iterates differently under
  every ``PYTHONHASHSEED``, so every process traced another program and
  a typed job never hit the compile cache (PR 30: 230-250 s of compile
  on every launch of the benchmark's typed cell). Two processes under
  hash seeds that used to disagree lower the same text."""
  import hashlib
  import os
  import subprocess
  import sys
  digests = set()
  here = os.path.dirname(os.path.abspath(__file__))
  for hash_seed in ('1', '3'):
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], capture_output=True,
        text=True, timeout=300, check=True,
        env=dict(os.environ, PYTHONHASHSEED=hash_seed, JAX_PLATFORMS='cpu',
                 PYTHONPATH=os.pathsep.join([os.path.dirname(here), here])))
    digests.add(out.stdout.strip().splitlines()[-1])
  assert len(digests) == 1, digests
  assert len(next(iter(digests))) == len(hashlib.sha256().hexdigest())


if __name__ == '__main__':
  import hashlib
  print(hashlib.sha256(_typed_chunk_text().encode()).hexdigest())


@pytest.mark.parametrize('slots', [7, 24, 1 << 16])
def test_blocked_run_attention_equals_one_piece(slots, monkeypatch):
  """A record's runs taken block by block (``_RUN_BLOCK_SLOTS``, each
  block rematerialised in the backward pass) give the values and the
  gradients of the one-piece kernel: runs are independent, so only the
  order in which the gather's transpose adds a child's cotangents may
  differ (1e-6). 7 slots: blocks of one run and a ragged tail's padding;
  24: blocks of several runs; 65,536 (the default): one piece."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.models import models as M
  rng = np.random.default_rng(5)
  n, f, k, heads, hd = 40, 11, 5, 2, 4
  w = jnp.asarray(rng.standard_normal((n, heads * hd)), jnp.float32)
  a_src = jnp.asarray(rng.standard_normal((n, heads)), jnp.float32)
  a_par = jnp.asarray(rng.standard_normal((f, heads)), jnp.float32)
  m = jnp.asarray(rng.random((f, k)) < 0.7).at[3].set(False)
  src = jnp.asarray(rng.integers(0, n, f * k), jnp.int32)

  def loss(fn, w, a_src, a_par):
    out = fn(w, a_src, a_par, m, src, heads, hd, 0.2)
    return (out * out).sum(), out

  (_, want_out), want_g = jax.jit(jax.value_and_grad(
      lambda *a: loss(M._gat_runs, *a), (0, 1, 2), has_aux=True))(
          w, a_src, a_par)
  monkeypatch.setattr(M, '_RUN_BLOCK_SLOTS', slots)
  (_, got_out), got_g = jax.jit(jax.value_and_grad(
      lambda *a: loss(M._gat_runs_blocked, *a), (0, 1, 2),
      has_aux=True))(w, a_src, a_par)
  np.testing.assert_array_equal(np.asarray(got_out), np.asarray(want_out))
  for g, wnt in zip(got_g, want_g):
    np.testing.assert_allclose(np.asarray(g), np.asarray(wnt), rtol=1e-6,
                               atol=1e-6)


def test_typed_gat_step_is_the_same_under_blocked_runs(monkeypatch):
  """The whole typed RGAT step — loss and every gradient leaf — with
  every record cut into blocks of at most 8 edge slots against the
  one-piece step, over a calibrated typed batch."""
  import jax
  from graphlearn_tpu.models import models as M
  make, model = typed_setup('gat', True)
  batch = train_lib.batch_to_dict(next(iter(make())))
  state, tx = train_lib.create_train_state(
      model, jax.random.PRNGKey(0), batch, lr=1e-2)

  def step():
    fn, _ = train_lib.make_train_step(model, tx, CLASSES)
    new, loss, _ = fn(state, batch)
    return float(loss), jax.device_get(new.params)

  loss_one, params_one = step()
  monkeypatch.setattr(M, '_RUN_BLOCK_SLOTS', 8)
  loss_blk, params_blk = step()
  assert loss_blk == loss_one
  for a, b in zip(jax.tree.leaves(params_blk), jax.tree.leaves(params_one)):
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
