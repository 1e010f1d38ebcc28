"""RunTrainer: whole-run-as-a-program contracts (loader/run_epoch.py).

The matrix, in order:

* **Bit-identity** — an E-epoch run's losses and final params equal E
  sequential ScanTrainer epochs EXACTLY (shuffle on and off, ragged
  tail batch, tail chunk) — the run program is a pure execution
  change, like the scanned epoch before it.
* **Dispatch budget** — ``ceil(E * steps / K) + 2`` instrumented
  dispatches for the whole run (vs ``E * (ceil(steps/K) + 2)`` for
  per-epoch scans), pinned under GLT_STRICT (conftest arms it here).
* **Early stop** — patience on the in-carry eval metric halts device
  work (no-op cond branches) with NO host fetch: the budget is
  unchanged, the stopped tail's losses are zeros, and the run report
  carries the stop point.
* **Crash + resume** — ChunkCheckpointer rides the inherited ack_hook
  seam; a crash mid-run resumes bit-identically at the last chunk
  boundary of the right epoch, eval carry included.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.models import GraphSAGE, train as train_lib

N, F, CLASSES = 96, 6, 3
FANOUTS = [3, 2]
BS = 8
STEPS = 6       # 44 seeds / bs 8 -> 5 full + ragged tail
K = 4           # 6 steps at K=4 -> tail chunk of 2 per epoch


def make_dataset(seed=0):
  rng = np.random.default_rng(seed)
  rows = np.repeat(np.arange(N), 4)
  cols = (rows + rng.integers(1, N, rows.shape[0])) % N
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=N)
  ds.init_node_features(rng.standard_normal((N, F)).astype(np.float32))
  ds.init_node_labels(rng.integers(0, CLASSES, N))
  return ds


def _pool(num=44):
  return np.random.default_rng(9).permutation(N)[:num].astype(np.int64)


def _make_loader(ds, num=44, **kw):
  kw.setdefault('batch_size', BS)
  kw.setdefault('shuffle', False)
  kw.setdefault('seed', 0)
  return glt.loader.NeighborLoader(ds, FANOUTS, _pool(num), **kw)


def _model_state(ds, tx=None):
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  first = train_lib.batch_to_dict(next(iter(_make_loader(ds))))
  if tx is None:
    state, tx = train_lib.create_train_state(model,
                                             jax.random.PRNGKey(0), first)
  else:
    state, _ = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                            first, optimizer=tx)
  return model, tx, state


@pytest.mark.parametrize('shuffle', [False, True])
def test_run_trainer_bit_identical_and_budget(shuffle):
  """E=3 epochs in ceil(E*steps/K)+2 dispatches, losses/params
  bit-identical to three sequential ScanTrainer epochs — ragged tail
  batch (44/8), tail chunk (6 steps at K=4), shuffle on/off."""
  import jax
  ds = make_dataset()
  epochs = 3

  model, tx, state_ref = _model_state(ds)
  ref = glt.loader.ScanTrainer(_make_loader(ds, shuffle=shuffle), model,
                               tx, CLASSES, chunk_size=K)
  ref_losses, ref_accs = [], []
  for _ in range(epochs):
    state_ref, lo, ac = ref.run_epoch(state_ref)
    ref_losses.append(np.asarray(lo))
    ref_accs.append(np.asarray(ac))
  ref_losses = np.concatenate(ref_losses)
  ref_accs = np.concatenate(ref_accs)
  assert ref_losses.shape == (epochs * STEPS,)

  _, _, state_run = _model_state(ds, tx=tx)
  trainer = glt.RunTrainer(_make_loader(ds, shuffle=shuffle), model, tx,
                           CLASSES, chunk_size=K, epochs=epochs)
  with glt.utils.count_dispatches() as dc:
    state_run, losses, accs = trainer.run(state_run)
  total = epochs * STEPS
  assert dc.total <= -(-total // K) + 2, dc
  assert dc.counts['run_scan_chunk'] == -(-total // K)
  np.testing.assert_array_equal(np.asarray(losses), ref_losses)
  np.testing.assert_array_equal(np.asarray(accs), ref_accs)
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state_run.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # stream continuation: both sides advanced identically
  assert trainer._sampler._call_count == ref._sampler._call_count
  assert trainer._epochs == ref._epochs
  # the in-carry eval report covered every epoch
  rep = jax.device_get(trainer.last_run_report)
  assert rep['epochs_run'] == epochs and not rep['stopped']
  assert np.isfinite(rep['eval_metric']).all()


def test_run_trainer_early_stop_in_carry():
  """min_delta=10 makes epoch 2 provably non-improving: with
  patience=1 the stop flag sets at the epoch-2 boundary IN-CARRY, the
  remaining epochs' steps run the no-op branch (zero losses), and the
  dispatch budget is UNCHANGED — no host round-trip anywhere decides
  or observes the stop until the caller reads the report."""
  import jax
  ds = make_dataset()
  epochs = 5
  model, tx, state = _model_state(ds)
  trainer = glt.RunTrainer(_make_loader(ds), model, tx, CLASSES,
                           chunk_size=K, epochs=epochs, patience=1,
                           min_delta=10.0)
  total = epochs * STEPS
  with glt.utils.count_dispatches() as dc:
    state, losses, accs = trainer.run(state)
  assert dc.total <= -(-total // K) + 2, dc   # stop cost ZERO dispatches
  losses = np.asarray(losses)
  assert losses.shape == (total,)
  # epochs 1-2 trained; the stopped tail is the no-op branch's zeros
  assert (losses[:2 * STEPS] != 0).all()
  assert (losses[2 * STEPS:] == 0).all()
  rep = jax.device_get(trainer.last_run_report)
  assert bool(rep['stopped']) and rep['epochs_run'] == 2
  assert np.isfinite(rep['eval_metric'][:2]).all()
  assert np.isnan(rep['eval_metric'][2:]).all()   # never reached
  # patience=None never stops (the bit-identity contract's mode)
  _, _, state2 = _model_state(ds, tx=tx)
  t2 = glt.RunTrainer(_make_loader(ds), model, tx, CLASSES,
                      chunk_size=K, epochs=2)
  t2.run(state2)
  assert not bool(jax.device_get(t2.last_run_report)['stopped'])


@pytest.mark.slow  # tier-1 budget (PR 18): RunTrainer variant of the
# crash-resume family — the scan and dist reps stay tier-1
def test_run_trainer_crash_resume_across_epoch_boundary(tmp_path):
  """ChunkCheckpointer rides the inherited ack_hook seam unchanged: a
  crash after chunk 2 (global step 8 — INSIDE epoch 2) resumes in a
  fresh trainer bit-identically, eval carry included, across the
  epoch boundary."""
  import jax

  from graphlearn_tpu.recovery import ChunkCheckpointer
  ds = make_dataset()
  epochs = 3
  mk = lambda: _make_loader(ds, shuffle=True)  # noqa: E731

  model, tx, state_ref = _model_state(ds)
  ref = glt.RunTrainer(mk(), model, tx, CLASSES, chunk_size=K,
                       epochs=epochs)
  state_ref, ref_losses, ref_accs = ref.run(state_ref)
  ref_losses = np.asarray(ref_losses)
  ref_rep = jax.device_get(ref.last_run_report)

  class Boom(Exception):
    pass

  _, _, state = _model_state(ds, tx=tx)
  victim = glt.RunTrainer(mk(), model, tx, CLASSES, chunk_size=K,
                          epochs=epochs)
  ckpt = ChunkCheckpointer(str(tmp_path), every=1).attach(victim)
  inner = victim.ack_hook
  calls = {'n': 0}

  def killer(c, start, k):
    inner(c, start, k)
    calls['n'] += 1
    if calls['n'] == 2:       # crash after global chunk 1 (step 8)
      raise Boom()

  victim.ack_hook = killer
  with pytest.raises(Boom):
    victim.run(state)
  ckpt.flush()
  ckpt.close()
  ckpt.detach()

  fresh = glt.RunTrainer(mk(), model, tx, CLASSES, chunk_size=K,
                         epochs=epochs)
  ck2 = ChunkCheckpointer(str(tmp_path)).attach(fresh)
  _, _, tmpl = _model_state(ds, tx=tx)
  state2, losses2, accs2 = ck2.resume_epoch(fresh, tmpl)
  np.testing.assert_array_equal(np.asarray(losses2), ref_losses)
  for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                  jax.tree_util.tree_leaves(state2.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # the restored eval carry reproduced the whole-run report exactly
  rep = jax.device_get(fresh.last_run_report)
  np.testing.assert_array_equal(rep['eval_metric'],
                                ref_rep['eval_metric'])
  assert rep['epochs_run'] == epochs
  ck2.close()
  ck2.detach()


def test_run_trainer_program_population():
  """One executable per program site: the compile run builds exactly
  one run_epoch_seeds + one run_scan_chunk per chunk LENGTH (full K +
  tail) + one concat; a steady-state run compiles nothing
  (retrace_budget 0 raises under GLT_STRICT on any overrun)."""
  import jax

  from graphlearn_tpu.metrics import programs
  ds = make_dataset()
  model, tx, state = _model_state(ds)
  trainer = glt.RunTrainer(_make_loader(ds), model, tx, CLASSES,
                           chunk_size=K, epochs=2)
  base = {s: programs.compile_count(s)
          for s in ('run_epoch_seeds', 'run_scan_chunk',
                    'run_metrics_concat')}
  state, losses, _ = trainer.run(state)   # compile run
  jax.block_until_ready(losses)
  assert programs.compile_count('run_epoch_seeds') - \
      base['run_epoch_seeds'] == 1
  # 12 steps at K=4: full chunks only -> ONE chunk-length executable
  assert programs.compile_count('run_scan_chunk') - \
      base['run_scan_chunk'] == 1
  with programs.retrace_budget('run_scan_chunk', 0):
    with programs.retrace_budget('run_epoch_seeds', 0):
      state, losses, _ = trainer.run(state)
      jax.block_until_ready(losses)


def test_run_trainer_validation():
  """Scope errors: padded-window sampling (host-side per-epoch table
  rebuild cannot fold into one program), bad epochs/patience."""
  ds = make_dataset()
  model, tx, _ = _model_state(ds)
  with pytest.raises(ValueError, match='padded'):
    glt.RunTrainer(_make_loader(ds, padded_window=4), model, tx,
                   CLASSES, epochs=2)
  with pytest.raises(ValueError, match='epochs'):
    glt.RunTrainer(_make_loader(ds), model, tx, CLASSES, epochs=0)
  with pytest.raises(ValueError, match='patience'):
    glt.RunTrainer(_make_loader(ds), model, tx, CLASSES, epochs=2,
                   patience=0)
  with pytest.raises(ValueError, match='track_eval'):
    glt.RunTrainer(_make_loader(ds), model, tx, CLASSES, epochs=2,
                   patience=1, track_eval=False)


def test_run_trainer_track_eval_off_bit_identical():
  """track_eval=False (the pure dispatch-tax mode) drops the per-step
  eval forward: losses stay bit-identical to the tracked run, the
  budget is unchanged, and the report's eval_metric stays NaN while
  epochs_run still counts."""
  import jax
  ds = make_dataset()
  model, tx, state_a = _model_state(ds)
  on = glt.RunTrainer(_make_loader(ds), model, tx, CLASSES,
                      chunk_size=K, epochs=2)
  state_a, losses_a, _ = on.run(state_a)

  _, _, state_b = _model_state(ds, tx=tx)
  off = glt.RunTrainer(_make_loader(ds), model, tx, CLASSES,
                       chunk_size=K, epochs=2, track_eval=False)
  with glt.utils.count_dispatches() as dc:
    state_b, losses_b, _ = off.run(state_b)
  assert dc.total <= -(-2 * STEPS // K) + 2
  np.testing.assert_array_equal(np.asarray(losses_b),
                                np.asarray(losses_a))
  for a, b in zip(jax.tree_util.tree_leaves(state_a.params),
                  jax.tree_util.tree_leaves(state_b.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  rep = jax.device_get(off.last_run_report)
  assert rep['epochs_run'] == 2 and not bool(rep['stopped'])
  assert np.isnan(rep['eval_metric']).all()
