"""Run-as-a-program: the whole multi-epoch training RUN as one scanned
program stream.

Epoch-as-a-program (scan_epoch.ScanTrainer) collapsed an epoch to
``ceil(steps/K) + 2`` dispatches, but a RUN of E epochs still pays that
per epoch — ``E * (ceil(steps/K) + 2)`` dispatches plus per-epoch host
Python (seed redraw, counter bookkeeping) — pure host time between
programs. :class:`RunTrainer` extends the contract one level up: the E-epoch
run executes as

    ``ceil(E * steps / K) + 2`` dispatches
    (1 run-seed program + chunks + 1 metrics concat)

with the per-epoch reseed FOLDED INTO the seed program (epoch ``e``'s
permutation key is ``fold_in(perm_key, epoch0 + e)`` — exactly the key
ScanTrainer would fold on the host — drawn for all E epochs in one
dispatch) and chunks crossing epoch boundaries freely.

The scan carry additionally threads:

* **on-device eval counts** (``models.train.make_eval_counts``): exact
  per-epoch correct/total over the training stream's seed slots,
  accumulated in-carry and converted to the epoch metric at each
  boundary — zero host fetches;
* **an early-stop flag**: patience on the eval metric, checked
  IN-CARRY at epoch boundaries. Once set, every later step runs the
  no-op branch of a ``lax.cond`` — stopped epochs become no-op chunks
  (the dispatches still land, the device work doesn't) with no host
  round-trip anywhere.

Bit-identity contract: with early-stop never firing, losses and final
params are BIT-IDENTICAL to E sequential ``ScanTrainer.run_epoch``
calls over the same loader (ragged tail, tail chunk, shuffle on or
off) — the eval forward is a pure read of the pre-update params and
perturbs nothing (tests/test_run_epoch.py pins the matrix). The
``stage_hook``/``ack_hook`` chunk-boundary seams carry the standard
contract, so ``recovery.ChunkCheckpointer`` attaches unchanged and a
mid-run crash resumes BIT-IDENTICALLY at the last chunk boundary of
the right epoch (the eval carry rides the snapshot's extra arrays).

Scope: the ScanTrainer scope MINUS padded-window sampling — the
padded table's per-epoch reseed is a host-side table rebuild that
cannot fold into one program stream (use per-epoch ScanTrainer there).
The run's overflow flag accumulates across ALL epochs and the loader's
overflow policy fires once, at run end.

Usage::

    trainer = RunTrainer(loader, model, tx, num_classes, chunk_size=32,
                         epochs=20, patience=3)
    state, losses, accs = trainer.run(state)
    report = trainer.last_run_report   # device arrays: fetch once
"""
from typing import Optional

import numpy as np

from .. import metrics
from ..metrics import programs, spans
from ..utils.strict import strict_guards
from ..utils.trace import record_dispatch
from .node_loader import NodeLoader
from .pipeline import refuse_link, refuse_tables, refuse_typed
from .scan_epoch import ScanTrainer


class RunTrainer(ScanTrainer):
  """Executes an E-epoch run as ``ceil(E * steps / K) + 2`` dispatches
  (module docstring).

  Args (beyond ScanTrainer's):
    epochs: E, the number of epochs the run program covers.
    patience: early-stop patience — stop after this many consecutive
      epochs whose eval metric failed to improve ``best + min_delta``
      (None disables early stop; the bit-identity contract's mode).
    min_delta: minimum improvement that resets the patience counter.
    track_eval: compute the in-carry eval counts (one extra model
      FORWARD per step — a pure read, bit-identity preserved either
      way). ``False`` drops that forward for runs that want the pure
      dispatch-tax win and no report metrics (``last_run_report``'s
      eval_metric stays NaN); required True when ``patience`` is set.
  """

  _NAME = 'RunTrainer'

  def __init__(self, loader: NodeLoader, model, tx, num_classes: int,
               chunk_size: int = 32, epochs: int = 1,
               patience: Optional[int] = None, min_delta: float = 0.0,
               seed_labels_only: Optional[bool] = None,
               perm_seed: Optional[int] = None,
               track_eval: bool = True):
    refuse_tables(loader, self._NAME)
    refuse_typed(loader, self._NAME)   # typed run chunks: ROADMAP M5
    refuse_link(loader, self._NAME)
    super().__init__(loader, model, tx, num_classes,
                     chunk_size=chunk_size,
                     seed_labels_only=seed_labels_only,
                     perm_seed=perm_seed)
    if epochs < 1:
      raise ValueError(f'epochs must be >= 1, got {epochs}')
    if patience is not None and patience < 1:
      raise ValueError(f'patience must be >= 1 or None, got {patience}')
    if patience is not None and not track_eval:
      raise ValueError('patience requires track_eval=True — the '
                       'early-stop flag is a function of the in-carry '
                       'eval metric')
    if getattr(self._sampler, 'padded_window', None) is not None:
      raise ValueError(
          f'{self._NAME} cannot fold padded-window sampling into one '
          'run program: the per-epoch padded-table reseed is a '
          'host-side adjacency rebuild (NodeLoader._begin_epoch). Run '
          'per-epoch ScanTrainer there, or drop padded_window')
    self.epochs = int(epochs)
    self.patience = None if patience is None else int(patience)
    self.min_delta = float(min_delta)
    self.track_eval = bool(track_eval)
    from ..models import train as train_lib
    self._eval_counts = (train_lib.make_eval_counts(model)
                         if self.track_eval else None)
    self._run_seed_fn = programs.instrument(self._build_run_seed_fn(),
                                            'run_epoch_seeds')
    self._run_chunk_fn = programs.instrument(self._build_run_chunk_fn(),
                                             'run_scan_chunk')
    self._run_concat_fn = programs.instrument(self._build_concat_fn(),
                                              'run_metrics_concat')
    #: device arrays from the final carry after each run: per-epoch
    #: eval metric [E] (NaN for epochs never reached), epochs_run,
    #: stopped flag, best metric — fetch once, after the run
    self.last_run_report = None
    self._resume_eval = None   # recovery: eval carry at the boundary

  # ------------------------------------------------------------- programs

  def _build_run_seed_fn(self):
    """ONE program for the RUN prologue: all E epochs' permutations
    (epoch ``e`` drawn under ``fold_in(perm_base, epoch0 + e)`` — the
    exact key ScanTrainer folds per epoch, so the flattened
    [E * steps, B] matrices are row-identical to E sequential epoch
    prologues), ragged tails masked per epoch."""
    import jax
    import jax.numpy as jnp
    batch = self._batch_size
    shuffle = self._shuffle

    def run_epoch_seeds(seeds, perm_base, epoch0, num_epochs, steps):
      n = seeds.shape[0]

      def one_epoch(e):
        key = jax.random.fold_in(perm_base, e)
        order = (jax.random.permutation(key, n) if shuffle
                 else jnp.arange(n, dtype=jnp.int32))
        total = steps * batch
        if total <= n:       # drop_last: the permutation's prefix
          order = order[:total]
          mask = jnp.ones((total,), bool)
        else:                # ragged tail, masked invalid
          order = jnp.concatenate(
              [order, jnp.zeros((total - n,), order.dtype)])
          mask = jnp.arange(total) < n
        seed_mat = jnp.where(mask, seeds[order], 0).reshape(steps,
                                                            batch)
        return seed_mat, mask.reshape(steps, batch)

      mats, masks = jax.vmap(one_epoch)(
          epoch0 + jnp.arange(num_epochs, dtype=jnp.int32))
      return (mats.reshape(num_epochs * steps, batch),
              masks.reshape(num_epochs * steps, batch))

    return jax.jit(run_epoch_seeds, static_argnums=(3, 4))

  def _build_run_chunk_fn(self):
    """The scanned K-step RUN program: the ScanTrainer chunk body plus
    the eval/early-stop carry. Global step ``g`` derives its epoch as
    ``g // S`` and its sampler count as ``count0 + g`` (the exact
    continuation of E sequential epochs' fold_in streams). The whole
    step body sits under a ``lax.cond`` on the stop flag: a stopped
    run's remaining chunks execute the no-op branch only."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    sample_collate = self._sample_collate
    train_step = self._train_step   # jit-of-jit: inlined into the scan
    eval_counts = self._eval_counts
    track_eval = self.track_eval
    patience = self.patience
    min_delta = self.min_delta

    def run_scan_chunk(state, ovf, ev, fargs, feats, id2i, labels,
                       seed_mat, mask_mat, base_key, count0, start, k,
                       steps_per_epoch):
      seeds_k = lax.dynamic_slice_in_dim(seed_mat, start, k, axis=0)
      masks_k = lax.dynamic_slice_in_dim(mask_mat, start, k, axis=0)
      gsteps = start + lax.iota(jnp.int32, k)

      def active(op, seeds, smask, g):
        state, ovf, ev = op
        key = jax.random.fold_in(base_key, count0 + g)
        batch, overflow = sample_collate(fargs, feats, id2i, labels,
                                         seeds, smask, key)
        is_end = (g + 1) % steps_per_epoch == 0
        if track_eval:
          # exact eval counts of the CURRENT params over this batch's
          # seed slots — a pure read; the train step below consumes
          # the same batch unperturbed (the bit-identity contract)
          correct, total = eval_counts(state.params, batch)
          state, loss, acc = train_step(state, batch)
          correct = ev['correct'] + correct.astype(jnp.int32)
          total = ev['total'] + total.astype(jnp.int32)
          e_idx = g // steps_per_epoch
          metric = correct.astype(jnp.float32) / \
              jnp.maximum(total, 1).astype(jnp.float32)
          improved = metric > ev['best'] + min_delta
          best = jnp.where(is_end & improved, metric, ev['best'])
          bad = jnp.where(is_end,
                          jnp.where(improved, jnp.int32(0),
                                    ev['bad'] + 1),
                          ev['bad'])
          stop = ev['stop']
          if patience is not None:
            stop = stop | (is_end & (bad >= patience))
          mets = jnp.where(
              is_end,
              lax.dynamic_update_index_in_dim(ev['metrics'], metric,
                                              e_idx, 0),
              ev['metrics'])
          ev = dict(correct=jnp.where(is_end, jnp.int32(0), correct),
                    total=jnp.where(is_end, jnp.int32(0), total),
                    best=best, bad=bad, stop=stop,
                    edone=ev['edone'] + is_end.astype(jnp.int32),
                    metrics=mets)
        else:
          # track_eval=False drops the per-step eval forward (the pure
          # dispatch-tax mode); the carry keeps its full structure so
          # recovery snapshots and the report shape stay uniform —
          # eval_metric remains NaN, epochs_run still counts
          state, loss, acc = train_step(state, batch)
          ev = dict(ev, edone=ev['edone'] + is_end.astype(jnp.int32))
        return ((state, ovf | overflow, ev),
                (loss.astype(jnp.float32), acc.astype(jnp.float32)))

      def stopped(op, seeds, smask, g):
        del seeds, smask, g
        return op, (jnp.float32(0), jnp.float32(0))

      def body(carry, xs):
        seeds, smask, g = xs
        _, _, ev = carry
        # once stopped, the whole sample+eval+train body is skipped —
        # the chunk dispatch lands but the device executes the no-op
        # branch (no host round-trip decides this, ever)
        return lax.cond(ev['stop'], stopped, active, carry, seeds,
                        smask, g)

      (state, ovf, ev), (losses, accs) = lax.scan(
          body, (state, ovf, ev), (seeds_k, masks_k, gsteps))
      return state, ovf, ev, losses, accs

    return jax.jit(run_scan_chunk, static_argnums=(12, 13),
                   donate_argnums=(0, 1, 2))

  # ----------------------------------------------------------------- run

  def _epoch_steps(self) -> int:
    # the RUN is the unit: the inherited run_epoch bracket sees
    # E * steps as "the epoch's" step count (budget, spans, flight)
    return len(self.loader._batcher) * self.epochs

  def run(self, state, max_steps: Optional[int] = None,
          start_step: int = 0, resume_overflow: bool = False):
    """The whole-run entry point (an alias of :meth:`run_epoch` — the
    checkpointer seam requires the standard name). Returns
    ``(state, losses, accs)`` with losses/accs [E * steps]-shaped
    device arrays; after an early stop the stopped tail is zeros and
    ``last_run_report`` carries the per-epoch metrics + stop point."""
    return self.run_epoch(state, max_steps=max_steps,
                          start_step=start_step,
                          resume_overflow=resume_overflow)

  def run_epoch(self, state, max_steps: Optional[int] = None,
                start_step: int = 0, resume_overflow: bool = False):
    metrics.inc('run.runs')
    metrics.inc('run.epochs_scheduled', self.epochs)
    # a zero-step run returns from the inherited early path before
    # _run_epoch_body assigns the report — None there, never a stale
    # report attributed to this run
    self.last_run_report = None
    with spans.span('run.train', emitter=self._NAME,
                    epochs=self.epochs, epoch0=self._epochs):
      return super().run_epoch(state, max_steps=max_steps,
                               start_step=start_step,
                               resume_overflow=resume_overflow)

  def _initial_eval_carry(self, num_epochs: int):
    import jax
    if self._resume_eval is not None:
      ev = {k: np.asarray(v) for k, v in self._resume_eval.items()}
      self._resume_eval = None
      return jax.device_put(ev)
    return jax.device_put(dict(
        correct=np.int32(0), total=np.int32(0),
        best=np.float32(-np.inf), bad=np.int32(0),
        stop=np.asarray(False), edone=np.int32(0),
        metrics=np.full((num_epochs,), np.nan, np.float32)))

  def _run_epoch_body(self, state, steps, full_steps, start_step=0,
                      resume_overflow=False):
    """The run program proper: one all-epochs seed draw + scanned
    chunks over the flattened step stream. Mirrors ScanTrainer's body;
    the inherited run_epoch owns the guard/flight bracketing."""
    import jax
    num_epochs = self.epochs
    steps_per_epoch = full_steps // num_epochs
    if self._seeds_dev is None:
      self._seeds_dev = jax.device_put(
          np.asarray(self.loader.input_seeds, dtype=np.int32))
    fargs = self._sampler._fused_args()
    base_key = self._sampler._key
    epoch0 = jax.device_put(np.int32(self._epochs))
    count0 = jax.device_put(np.int32(self._sampler._call_count + 1))
    ovf = jax.device_put(np.asarray(bool(resume_overflow)))
    ev = self._initial_eval_carry(num_epochs)
    losses, accs = [], []
    start = start_step
    with strict_guards():
      record_dispatch('run_epoch_seeds')
      seed_mat, mask_mat = self._run_seed_fn(
          self._seeds_dev, self._perm_key, epoch0, num_epochs,
          steps_per_epoch)
      while start < steps:
        k = min(self.chunk_size, steps - start)
        if self.stage_hook is not None:
          self.stage_hook(start // self.chunk_size, start, k)
        record_dispatch('run_scan_chunk')
        with spans.span('epoch.chunk', start=start, k=k):
          state, ovf, ev, loss_k, acc_k = self._run_chunk_fn(
              state, ovf, ev, fargs, self._feats, self._id2i,
              self._labels, seed_mat, mask_mat, base_key, count0,
              jax.device_put(np.int32(start)), k, steps_per_epoch)
        losses.append(loss_k)
        accs.append(acc_k)
        self._steps_dispatched = start + k
        if self.ack_hook is not None:
          # boundary carry for the recovery seam — valid only inside
          # the hook call (the next chunk donates state/ovf/eval)
          self._chunk_carry = dict(state=state, ovf=ovf, eval=ev,
                                   losses=losses, accs=accs,
                                   steps=steps, full_steps=full_steps,
                                   start_step=start_step)
          self.ack_hook(start // self.chunk_size, start, k)
        start += k
      if len(losses) > 1:
        record_dispatch('run_metrics_concat')
        losses, accs = self._run_concat_fn(losses, accs)
      else:
        losses, accs = losses[0], accs[0]
    self.last_run_report = dict(eval_metric=ev['metrics'],
                                best_metric=ev['best'],
                                epochs_run=ev['edone'],
                                stopped=ev['stop'])
    # keep the host fold_in stream aligned with the RUN's consumption:
    # counter addressing is positional, so the host position advances
    # by the scheduled steps whether or not early-stop no-op'd a tail
    # (later sampling continues the same deterministic stream)
    self._sampler._call_count += steps
    self._epochs += num_epochs
    return state, losses, accs, ovf

  def _flight_config(self) -> dict:
    cfg = super()._flight_config()
    cfg.update(epochs=self.epochs, patience=self.patience,
               min_delta=self.min_delta, track_eval=self.track_eval)
    return cfg

  # -------------------------------------------------- recovery protocol
  # (recovery/checkpoint.py ChunkCheckpointer rides the inherited
  # stage/ack seams unchanged; the run adds only the eval carry to the
  # boundary snapshot)

  def _recovery_capture(self, carry):
    meta, dev = super()._recovery_capture(carry)
    meta['epochs_total'] = self.epochs
    ev = carry.get('eval')
    if ev is not None:
      for key, val in ev.items():
        dev[f'eval:{key}'] = val
    return meta, dev

  def _recovery_load(self, meta, arrays):
    ev = {k[len('eval:'):]: np.asarray(v)
          for k, v in (arrays or {}).items() if k.startswith('eval:')}
    rest = {k: v for k, v in (arrays or {}).items()
            if not k.startswith('eval:')}
    super()._recovery_load(meta, rest)
    self._resume_eval = ev or None

  def _recovery_advance(self, meta):
    """A completed-RUN snapshot advances past all E epochs."""
    self._sampler.load_state_dict(meta['sampler'])
    self._sampler._call_count += int(meta['steps'])
    self._epochs = int(meta['epoch']) + int(meta.get('epochs_total', 1))
