"""ScanTrainer: scanned-epoch equivalence + dispatch-count contracts.

The scanned epoch must be a pure EXECUTION change: with shuffle=False the
fold_in key stream matches the per-step loader loop's
(sampler._next_key discipline), so losses and final params are identical
— including a ragged tail (steps not divisible by the scan chunk K). The
dispatch counter then pins the point of the whole subsystem: one epoch
issues at most ceil(steps/K) + 2 instrumented dispatches instead of
~3 per step.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.models import GAT, GraphSAGE, train as train_lib


def make_dataset(n=96, f=6, seed=0, degree=4):
  rng = np.random.default_rng(seed)
  rows = np.repeat(np.arange(n), degree)
  cols = (rows + rng.integers(1, n, rows.shape[0])) % n
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=n)
  ds.init_node_features(rng.standard_normal((n, f)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, 3, n))
  return ds


def _make_loader(ds, num_seeds, fanouts=(3, 2), **kw):
  kw.setdefault('batch_size', 8)
  kw.setdefault('shuffle', False)
  kw.setdefault('seed', 0)
  # a NON-arange seed pool: pool[0] != 0 catches any tail padding that
  # differs from the host path's literal node-id-0 padding
  n = ds.get_graph().num_nodes
  pool = (np.random.default_rng(9).permutation(n)[:num_seeds]
          .astype(np.int64))
  return glt.loader.NeighborLoader(ds, list(fanouts), pool, **kw)


def _fresh_state(model, tx_template_batch):
  import jax
  return train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                      tx_template_batch)


@pytest.mark.parametrize('size', ['small', 'tiled'])
def test_scan_trainer_matches_per_step_loop(size):
  """shuffle=False scanned epoch == the plain per-step loader loop:
  identical per-step losses and final params, with a ragged tail batch
  (44 seeds / batch 8 -> 5 full + 1 tail) and a tail CHUNK (6 steps at
  K=4 -> chunks of 4 and 2). 'tiled': the same identity under calibrated
  caps wide enough that ops.uniform_sample draws hop 1 tile by tile
  (1340 seeds / batch 256 -> 5 full + 1 tail)."""
  if size == 'small':
    ds, num_seeds, kw = make_dataset(), 44, {}
  else:
    ds, num_seeds = make_dataset(n=6000, degree=12), 1340
    kw = dict(fanouts=(10, 2), batch_size=256, frontier_caps='auto')
    from graphlearn_tpu.ops.neighbor import draw_tile_rows
    caps = _make_loader(ds, num_seeds, **kw).sampler.hop_caps(256)
    assert draw_tile_rows(caps[1]), caps    # hop 1's frontier is tiled
  model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  make = lambda: _make_loader(ds, num_seeds, **kw)

  # template batch from a throwaway loader so neither run's key stream
  # is consumed by model init
  first = train_lib.batch_to_dict(next(iter(make())))

  # ---- reference: plain per-step loop
  import jax
  ref_loader = make()
  state_ref, tx = _fresh_state(model, first)
  step, _ = train_lib.make_train_step(model, tx, 3)
  losses_ref = []
  for b in ref_loader:
    state_ref, loss, _ = step(state_ref, train_lib.batch_to_dict(b))
    losses_ref.append(np.asarray(loss))
  assert len(losses_ref) == 6   # 5 full + ragged tail

  # ---- scanned epoch over an identical fresh loader
  scan_loader = make()
  state_scan, _ = train_lib.create_train_state(
      model, jax.random.PRNGKey(0), first, optimizer=tx)
  trainer = glt.loader.ScanTrainer(scan_loader, model, tx, 3,
                                   chunk_size=4)
  state_scan, losses, accs = trainer.run_epoch(state_scan)
  losses = np.asarray(losses)
  assert losses.shape == (6,) and np.asarray(accs).shape == (6,)
  np.testing.assert_allclose(losses, np.asarray(losses_ref).reshape(-1),
                             rtol=0, atol=0)
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state_scan.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # the sampler's host key counter advanced exactly one epoch: a SECOND
  # epoch of both runs still matches (stream continuation)
  assert scan_loader.sampler._call_count == ref_loader.sampler._call_count

  for b in ref_loader:
    state_ref, loss, _ = step(state_ref, train_lib.batch_to_dict(b))
  state_scan, losses2, _ = trainer.run_epoch(state_scan)
  assert np.asarray(losses2).shape == (6,)
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state_scan.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scan_trainer_drop_last_and_shuffle():
  """drop_last epochs scan the permutation prefix (no tail batch), and
  the on-device shuffle covers every seed exactly once per epoch."""
  ds = make_dataset()
  loader = _make_loader(ds, 40, shuffle=True, drop_last=True)
  model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  first = train_lib.batch_to_dict(
      next(iter(_make_loader(ds, 40, drop_last=True))))
  import jax
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  trainer = glt.loader.ScanTrainer(loader, model, tx, 3, chunk_size=3)
  # the permutation program covers each seed once: check via the seed
  # matrix itself (one epoch = 5 full batches over 40 seeds)
  seeds_dev = jax.numpy.asarray(np.arange(40, dtype=np.int32))
  perm_key = jax.random.fold_in(trainer._perm_key, 0)
  seed_mat, mask_mat = trainer._seed_fn(seeds_dev, perm_key, 5)
  assert seed_mat.shape == (5, 8) and bool(np.asarray(mask_mat).all())
  assert sorted(np.asarray(seed_mat).reshape(-1).tolist()) == list(
      range(40))
  state, losses, accs = trainer.run_epoch(state)
  assert np.asarray(losses).shape == (5,)
  assert np.isfinite(np.asarray(losses)).all()
  # epoch 2 shuffles differently (epoch index folds into the perm key)
  seed_mat2, _ = trainer._seed_fn(seeds_dev,
                                  jax.random.fold_in(trainer._perm_key, 1),
                                  5)
  assert not np.array_equal(np.asarray(seed_mat), np.asarray(seed_mat2))


def test_scan_trainer_overflow_guard():
  """Calibrated-caps overflow rides the scan carry: 'raise' fires at
  epoch end with zero in-epoch syncs; a max_steps break defers to
  check_overflow(); 'recompute' is refused at construction."""
  import jax
  ds = make_dataset()
  mk = lambda **kw: _make_loader(ds, 32, dedup='merge', **kw)

  def trainer_for(loader, chunk=4):
    model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
    first = train_lib.batch_to_dict(next(iter(mk())))
    state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                             first)
    return glt.loader.ScanTrainer(loader, model, tx, 3,
                                  chunk_size=chunk), state

  tr, state = trainer_for(mk(frontier_caps=[1, 1]))
  with pytest.raises(RuntimeError, match='frontier_caps overflowed'):
    tr.run_epoch(state)

  tr, state = trainer_for(mk(frontier_caps=[1, 1]))
  state, _, _ = tr.run_epoch(state, max_steps=2)
  assert tr.loader.check_overflow()

  tr, state = trainer_for(mk(frontier_caps='auto'))
  state, losses, _ = tr.run_epoch(state)
  assert len(losses) == 4 and np.isfinite(float(losses[0]))

  with pytest.raises(ValueError, match='recompute'):
    trainer_for(mk(frontier_caps=[1, 1], overflow_policy='recompute'))


def test_scan_trainer_dispatch_count():
  """A scanned epoch issues <= ceil(steps/K) + 2 instrumented dispatches
  (chunks + seed-matrix prologue + metrics concat), where the per-step
  loop issues ~3 per step. The program observatory rides the same
  epoch: compile_count == the executable population (one per chunk
  LENGTH) under GLT_STRICT, and a steady-state epoch compiles nothing
  — recorded with zero extra dispatches (dc bit-matches the budget
  with the observatory armed)."""
  import jax

  from graphlearn_tpu.metrics import programs
  ds = make_dataset()
  num_seeds = 44     # 6 steps at batch 8 (ragged tail)
  chunk = 4          # ceil(6/4) = 2 chunk dispatches
  model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  first = train_lib.batch_to_dict(next(iter(_make_loader(ds, num_seeds))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  trainer = glt.loader.ScanTrainer(_make_loader(ds, num_seeds), model, tx,
                                   3, chunk_size=chunk)
  c0 = programs.compile_count('scan_chunk')   # observatory is global
  state, _, _ = trainer.run_epoch(state)   # compile outside the count
  # ONE executable per chunk length: the full-K chunk + the tail chunk
  assert programs.compile_count('scan_chunk') - c0 == 2
  steps = 6
  with programs.retrace_budget('scan_chunk', 0):   # steady state
    with glt.utils.count_dispatches() as dc:
      state, losses, _ = trainer.run_epoch(state)
  assert len(losses) == steps
  assert dc.total <= -(-steps // chunk) + 2, dc
  assert dc.counts['scan_chunk'] == -(-steps // chunk)
  assert programs.compile_count('scan_chunk') - c0 == 2   # no retrace

  # contrast: the plain per-step loop pays >= 2 dispatches per step
  # (sample + collate; its train step is the caller's own dispatch)
  loader = _make_loader(ds, num_seeds)
  with glt.utils.count_dispatches() as dc_loop:
    for _ in loader:
      pass
  assert dc_loop.total >= 2 * steps
  assert dc_loop.counts['sample'] == steps


def test_scan_dispatch_budget_with_fused_hop_kernel_routed():
  """ISSUE 13 acceptance: routing the fused sample+gather Pallas hop
  into the scanned epoch (use_fused_hop='interpret' exercises the real
  kernel through the interpreter inside the scan body) keeps the epoch
  at <= ceil(steps/K) + 2 dispatches under GLT_STRICT (conftest arms it
  for this module) — the kernel lives INSIDE the chunk program, it adds
  no dispatch sites — and the epoch stays bit-identical to the
  XLA-hop scanned epoch: same fold_in counters, same edges, same
  losses, same params."""
  import jax
  ds = make_dataset()
  num_seeds = 44     # 6 steps at batch 8 (ragged tail), chunk 4
  chunk, steps = 4, 6
  model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  first = train_lib.batch_to_dict(next(iter(_make_loader(ds, num_seeds))))
  state_ref, tx = train_lib.create_train_state(
      model, jax.random.PRNGKey(0), first)
  ref = glt.loader.ScanTrainer(_make_loader(ds, num_seeds), model, tx, 3,
                               chunk_size=chunk)
  state_ref, losses_ref, _ = ref.run_epoch(state_ref)

  fh_loader = _make_loader(ds, num_seeds, use_fused_hop='interpret')
  assert fh_loader.sampler.use_fused_hop == 'interpret'
  state_fh, _ = train_lib.create_train_state(
      model, jax.random.PRNGKey(0), first, optimizer=tx)
  trainer = glt.loader.ScanTrainer(fh_loader, model, tx, 3,
                                   chunk_size=chunk)
  state_fh, losses_fh, _ = trainer.run_epoch(state_fh)   # compile epoch
  np.testing.assert_array_equal(np.asarray(losses_fh),
                                np.asarray(losses_ref))
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state_fh.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # steady-state budget with the kernel routed in
  with glt.utils.count_dispatches() as dc:
    state_fh, losses_fh, _ = trainer.run_epoch(state_fh)
  assert len(losses_fh) == steps
  assert dc.total <= -(-steps // chunk) + 2, dc


def test_retrace_budget_catches_chunk_length_perturbation():
  """Acceptance (PR 8): deliberately perturbing the chunk length
  retraces the chunk program, retrace_budget catches it under
  GLT_STRICT (conftest arms it for this module), and the error names
  the changed argument — the static chunk-length k — in a
  human-readable signature diff."""
  import jax

  from graphlearn_tpu.metrics import programs
  from graphlearn_tpu.metrics.programs import RetraceBudgetExceeded
  ds = make_dataset()
  num_seeds = 32     # 4 steps at batch 8, chunk 4: ONE chunk length
  model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2)
  first = train_lib.batch_to_dict(next(iter(_make_loader(ds, 32))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  trainer = glt.loader.ScanTrainer(_make_loader(ds, num_seeds), model,
                                   tx, 3, chunk_size=4)
  c0 = programs.compile_count('scan_chunk')
  state, _, _ = trainer.run_epoch(state)
  assert programs.compile_count('scan_chunk') - c0 == 1
  # perturb the chunk length: the next epoch needs a NEW executable —
  # exactly the silent production retrace the budget exists to catch
  # (K=2 divides the 4 steps, so the epoch adds exactly one length)
  trainer.chunk_size = 2
  with pytest.raises(RetraceBudgetExceeded) as ei:
    with programs.retrace_budget('scan_chunk', 0):
      state, _, _ = trainer.run_epoch(state)
  msg = str(ei.value)
  assert 'scan_chunk' in msg and 'last retrace' in msg
  # the diff names the changed argument: the static k, 4 -> 2
  assert 'static:4 -> static:2' in msg, msg
  # the run itself completed — the budget is a guard rail, not a wedge
  assert programs.compile_count('scan_chunk') - c0 >= 2
  ev = programs.last_compile('scan_chunk')
  assert ev.index >= 1 and 'arg ' in ev.diff


def _op_names(compiled_text):
  import re
  return re.findall(r'op_name="([^"]*)"', compiled_text)


def _abstract(args):
  import jax
  return jax.tree.map(
      lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
      if hasattr(a, 'shape') else a, args)


@pytest.mark.parametrize('model_cls', [GraphSAGE, GAT])
def test_layer_scopes_are_in_the_scanned_body_and_the_per_batch_programs(
    model_cls):
  """The layer clock: every instruction of a layer carries its glt.*
  scope in op_name — inside the while body of the chunk program, and in
  the per-batch programs, whose names the benchmark reads, unchanged."""
  import jax
  import jax.numpy as jnp
  ds = make_dataset()
  model = model_cls(hidden_dim=8, out_dim=3, num_layers=2)
  b0 = next(iter(_make_loader(ds, 24)))
  first = train_lib.batch_to_dict(b0)
  state, tx = _fresh_state(model, first)
  loader = _make_loader(ds, 24)
  trainer = glt.loader.ScanTrainer(loader, model, tx, 3, chunk_size=3)
  seen, real = [], trainer._chunk_fn

  def spy(*args):
    seen.append(_abstract(args))
    return real(*args)

  trainer._chunk_fn = spy
  trainer.run_epoch(state)
  chunk = real.lower(*seen[0]).compile().as_text()
  assert 'HloModule jit_scan_epoch_chunk' in chunk
  names = _op_names(chunk)
  in_body = [n for n in names
             if n.startswith('jit(scan_epoch_chunk)/while/body/')]
  wanted = ['glt.sample/hop0/draw', 'glt.sample/hop1/induce',
            'glt.collate', 'glt.train/fwd_bwd', 'glt.train/update']
  for scope in wanted:
    assert any(f'/{scope}/' in n for n in in_body), scope
  # nothing of a layer sits outside the loop under the layer's name
  assert not [n for n in names if 'glt.' in n and n not in in_body
              and n.startswith('jit(scan_epoch_chunk)/')]

  sampler = loader.sampler
  seeds = jnp.zeros((8,), jnp.int32)
  sample = trainer._sample_fn.lower(
      *_abstract(sampler._fused_args()), seeds, seeds > -1,
      jax.random.PRNGKey(0)).compile().as_text()
  collate = glt.ops.collate_batch.lower(
      b0.node, jnp.int32(1), b0.edge_index[0], b0.edge_index[1],
      trainer._feats, trainer._id2i, trainer._labels, None, None,
      label_cap=trainer._label_cap).compile().as_text()
  step, _ = train_lib.make_train_step(model, tx, 3)
  train = step.lower(_fresh_state(model, first)[0],
                     first).compile().as_text()
  for text, module, scopes in [
      (sample, 'jit_sample_', wanted[:2]),
      (collate, 'jit_collate_batch', wanted[2:3]),
      (train, 'jit_train_step', wanted[3:])]:
    assert f'HloModule {module}' in text
    for scope in scopes:
      assert any(f'/{scope}/' in n for n in _op_names(text)), scope


def test_wrap_dispatch_counts_user_calls():
  """utils.wrap_dispatch: the explicit counting wrapper for dispatch
  sites outside the package (bench loops, user train steps)."""
  calls = []
  fn = glt.utils.wrap_dispatch(lambda x: calls.append(x) or x + 1,
                               'user_step')
  with glt.utils.count_dispatches() as dc:
    assert fn(1) == 2 and fn(2) == 3
  assert dc.counts == {'user_step': 2} and dc.total == 2
  # outside a counting region the wrapper is pass-through
  assert fn(3) == 4
  assert dc.total == 2


def test_conftest_virtual_cpu_mesh():
  """conftest must deliver the 8-device virtual CPU mesh the
  sharding/collective tests assume."""
  import jax
  assert jax.default_backend() == 'cpu'
  assert len(jax.devices()) == 8
