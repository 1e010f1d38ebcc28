"""Epoch-as-a-program: scanned K-step sample -> collate -> train execution.

The per-step loop pays three program launches per batch plus per-step
host numpy (seed padding), and the device idles while the host does it
(~9 % over two traced steps of the products path on a v5e — PERF.md
"Bring-up (PR 21)"; not yet a benchmark number). The reference hides
sampling latency with producer processes/streams
(dist_sampling_producer.py); on TPU the native answer is to put the
LOOP ITSELF on device: `ScanTrainer` executes an epoch as
~ceil(steps/K) dispatches — a `lax.scan` over a static chunk of K steps
whose body is the existing fused sample+collate+train program
(`pipeline.FusedEpochTrainer` plumbing).

Design points:
  * The epoch's seed permutation is drawn ON DEVICE
    (`jax.random.permutation` over the input-seed array, reshaped to
    [steps, B] with a validity mask for the ragged tail). The host
    `SeedBatcher` remains the shuffle=False / mid-epoch-resume path; the
    device permutation is a different (but equally uniform) stream.
  * PRNG keys are derived INSIDE the scan body via
    `fold_in(base_key, count)` with the same host-counter discipline as
    `NeighborSampler._next_key` — global step g uses
    count = call_count_at_epoch_start + 1 + g, so a shuffle=False scanned
    epoch replays the per-step loader loop's draws EXACTLY (equivalence-
    tested), and the sampler's counter is advanced afterwards so later
    sampling continues the same stream.
  * Losses/accuracies come back as [K] scan outputs; the calibrated-caps
    overflow flag accumulates in the carry — zero host syncs inside the
    epoch. overflow_policy='recompute' is rejected (it needs a
    per-batch host sync).
  * The train state is DONATED across chunk dispatches, so HBM stays
    flat at one state + one in-flight chunk. The state passed INTO
    run_epoch is consumed — use the returned state.

Dispatch budget per epoch: ceil(steps/K) chunk programs + 1 seed-matrix
program + 1 loss/acc concatenation = ceil(steps/K) + 2
(tests/test_scan_epoch.py asserts it via utils.count_dispatches).

Composes with every fused fast path (tree/block/padded sampling,
tree_dense / merge_dense models, seed_labels_only); the per-epoch
padded-table reseed runs between epochs like the plain loader
(`NodeLoader._begin_epoch`), and `_fused_args()` is re-fetched each epoch
so the chunks see the fresh table. On CPU the same programs run
unchanged (donation is a no-op there); only the dispatch-tax WIN
disappears, not correctness.

Usage:
    loader = NeighborLoader(ds, fanouts, idx, batch_size=B, shuffle=True,
                            drop_last=True, ...)
    trainer = ScanTrainer(loader, model, tx, num_classes, chunk_size=32)
    state, losses, accs = trainer.run_epoch(state)   # arrays stay on device

`DistScanTrainer` (below) is the DISTRIBUTED counterpart: the same
epoch-as-a-program contract over the collocated mesh loop, with the
scan body composing the sharded sampler's all_to_all hop engine, the
cached miss-only feature exchange, and the pmean'd data-parallel train
step inside ONE shard_map chunk program (PERF.md 'Scanned distributed
epoch'). The REMOTE (server-client) topology gets the same contract
from `distributed.RemoteScanTrainer` (docs/remote_scan.md): sampling
servers replay the counter-addressed stream into K-batch blocks and
the client scans a train-only chunk program over device-resident
blocks — same ceil(steps/K)+2 budget, same stage/ack hook seams, ack
and failover at chunk granularity.
"""
from typing import Optional

import numpy as np

from ..metrics import programs, spans
from ..utils.strict import strict_guards
from ..utils.trace import record_dispatch
from ..metrics.registry_names import SCOPE_SAMPLE, SCOPE_SEEDS
from .link_loader import LinkLoader
from .node_loader import NodeLoader
from .pipeline import (_RECOMPUTE_MSG, DistFusedEpochTrainer,
                       FusedEpochTrainer)


#: rounds of the epoch order's Feistel network (an alternating unbalanced
#: one: each round xors one half with a hash of the other)
_ORDER_ROUNDS = 6


def keyed_order(key, n: int, positions):
  """Where a shuffled epoch over ``n`` seeds visits, at ``positions``
  (int32, any shape, each in ``[0, n)``): a bijection on ``[0, n)`` drawn
  from ``key`` and evaluated for just those positions — nothing of size
  ``n`` is sorted or built, so a call of ``max_steps`` steps pays for
  ``max_steps * batch`` evaluations whatever ``n`` is.

  A Feistel network over the ``ceil(log2 n)`` bits of a position
  (``_ORDER_ROUNDS`` rounds, round keys from ``key``) is a bijection on
  ``[0, 2^bits)``; cycle-walking — apply it again while the value is not
  under ``n`` — restricts it to one on ``[0, n)``. ``2^bits < 2n``, so a
  walk takes under two applications on average."""
  import jax
  import jax.numpy as jnp
  bits = max(2, int(n - 1).bit_length())
  lo_bits = bits // 2
  hi_bits = bits - lo_bits
  lo_mask, hi_mask = (1 << lo_bits) - 1, (1 << hi_bits) - 1
  round_keys = jax.random.bits(key, (_ORDER_ROUNDS,), jnp.uint32)

  def mix(v, k):
    h = (v + k) * jnp.uint32(0x9E3779B1)
    h = (h ^ (h >> 15)) * jnp.uint32(0x85EBCA6B)
    return h ^ (h >> 13)

  def feistel(x):
    hi, lo = x >> lo_bits, x & jnp.uint32(lo_mask)
    for r in range(_ORDER_ROUNDS):
      if r % 2 == 0:
        hi = hi ^ (mix(lo, round_keys[r]) & jnp.uint32(hi_mask))
      else:
        lo = lo ^ (mix(hi, round_keys[r]) & jnp.uint32(lo_mask))
    return (hi << lo_bits) | lo

  x = feistel(positions.astype(jnp.uint32))
  x = jax.lax.while_loop(
      lambda x: jnp.any(x >= n),
      lambda x: jnp.where(x >= n, feistel(x), x), x)
  return x.astype(jnp.int32)


def _recovery_config_for(trainer) -> dict:
  """The snapshot-fingerprint config (recovery/checkpoint.py): the
  flight grouping config PLUS every stream-determining knob it omits —
  sampler strategy/dedup/padded-window/weighting and a digest of the
  seed pool itself. The resume refusal must catch any drift that would
  change the replayed draws, not just the coarse shape (a
  padded_window added to an 'identical' loader samples a different
  stream at the same fanouts/batch/seed)."""
  import hashlib
  s = trainer._sampler
  cfg = trainer._flight_config()
  seeds = getattr(trainer.loader, 'input_seeds', None)
  if seeds is None:
    # a link loader's seed edges may live on the device (and be every
    # edge of the graph): the digest covers their number, not a fetch
    seeds = [len(trainer.loader.rows)]
  cfg.update(
      strategy=getattr(s, 'strategy', None),
      dedup=getattr(s, 'dedup', None),
      padded_window=getattr(s, 'padded_window', None),
      weighted=str(getattr(s, 'with_weight', None)),
      frontier_caps=str(getattr(s, 'frontier_caps', None)),
      seeds_sha=hashlib.sha1(
          np.ascontiguousarray(
              np.asarray(seeds, np.int64)).tobytes()).hexdigest()[:16])
  return cfg


class ScanTrainer(FusedEpochTrainer):
  """Executes an epoch as ~ceil(steps/K) scanned-chunk dispatches.

  Args:
    loader: a NeighborLoader on the fused sampler path with
      device-resident features and labels — homogeneous, or over a
      typed graph with seeds of one node
      type: the chunk then traces the typed hop loop and the per-type
      collate (pipeline.FusedEpochTrainer), under the per-batch typed
      loader's own keys, and everything else here is the same code. Or
      a LinkNeighborLoader (homogeneous, binary or no negatives): the
      chunk then traces the sampler's link body and the pair step, a
      step's seeds are ``batch_size`` seed EDGES, ``num_classes`` is not
      asked for, and a shuffled epoch's order is ``keyed_order`` — a
      keyed bijection evaluated for the steps a call runs, where a node
      job draws ``jax.random.permutation`` over all its seeds.
    chunk_size: K, the static number of steps per scanned dispatch. The
      tail chunk (steps % K) compiles once more at its own length; pick
      K to divide the epoch when compile count matters.
    perm_seed: base seed for the ON-DEVICE epoch permutation (default:
      the loader's seed). Folded with the epoch index, so every epoch
      shuffles differently yet replayably.
  """

  _NAME = 'ScanTrainer'

  # chunk-boundary staging hooks (storage/ subsystem, docs/storage.md;
  # recovery/ checkpointing, docs/recovery.md): ``stage_hook(
  # chunk_index, start, k)`` runs on the dispatch thread BEFORE each
  # chunk dispatch, ``ack_hook(chunk_index, start, k)`` right after it
  # — the seam the out-of-core pipeline and the ChunkCheckpointer
  # attach to without subclassing the epoch loop. Host-side only; the
  # loop runs under strict_guards, so a hook may fetch device arrays
  # EXPLICITLY (jax.device_get — the checkpointer's boundary capture)
  # but must never transfer implicitly or dispatch programs. Inside
  # ack_hook, ``self._chunk_carry`` exposes the boundary state.
  stage_hook = None
  ack_hook = None

  def __init__(self, loader: NodeLoader, model, tx,
               num_classes: Optional[int] = None,
               chunk_size: int = 32,
               seed_labels_only: Optional[bool] = None,
               perm_seed: Optional[int] = None):
    import jax
    super().__init__(loader, model, tx, num_classes, seed_labels_only)
    # per chunk, the batch source's [k, n] per-step counts — a link
    # body's negatives tested, rejected, padded and rows of the seed
    # union; a typed source's valid learned-table slots — a scan output,
    # summed on the host once an epoch (_publish_step_counts empties it)
    self._step_counts = []
    if isinstance(loader, LinkLoader):
      self._count_names = ('link.negatives.tested',
                           'link.negatives.rejected',
                           'link.negatives.padded', 'link.seeds.unique')
    else:
      self._count_names = ('table.rows_read',) if self._id_types else ()
    if chunk_size < 1:
      raise ValueError(f'chunk_size must be >= 1, got {chunk_size}')
    self.chunk_size = int(chunk_size)
    self._shuffle = loader._batcher.shuffle
    self._drop_last = loader._batcher.drop_last
    if perm_seed is None:
      perm_seed = loader._batcher.seed or 0
    # tag the perm stream off fold_in(2**32 - 1): the sampler's step
    # keys are fold_in(PRNGKey(seed), count >= 1) on the SAME default
    # seed, and epoch e's permutation must not reuse step e's random
    # words; the tag sits where no host step counter can ever land
    self._perm_key = jax.random.fold_in(jax.random.PRNGKey(perm_seed),
                                        0xFFFFFFFF)
    self._epochs = 0        # folds into the perm key: fresh shuffle/epoch
    self._seeds_dev = None  # input seeds, uploaded once
    # program-observatory instrumentation under the record_dispatch
    # site names: compile/retrace detection (+ signature diffs) rides
    # every dispatch as one host-side cache-size read — the "ONE
    # executable per chunk length" contract becomes observable, and
    # retrace_budget can enforce it (metrics/programs.py)
    self._seed_fn = programs.instrument(self._build_seed_fn(),
                                        'epoch_seeds')
    self._chunk_fn = programs.instrument(self._build_chunk_fn(),
                                         'scan_chunk')
    self._concat_fn = programs.instrument(self._build_concat_fn(),
                                          'metrics_concat')

  # ------------------------------------------------------------- programs

  def _build_seed_fn(self):
    """ONE program for the epoch prologue: permutation draw + seed
    gather + [steps, B] reshape + ragged-tail validity mask."""
    import jax
    import jax.numpy as jnp
    batch = self._batch_size
    shuffle = self._shuffle
    def epoch_seeds(seeds, key, steps):
      n = seeds.shape[0]
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
      # pad slots carry node id 0 — the HOST loop's np.zeros padding —
      # so a scanned batch is byte-identical to sample_from_nodes' input
      seed_mat = jnp.where(mask, seeds[order], 0).reshape(steps, batch)
      return seed_mat, mask.reshape(steps, batch)

    return jax.jit(epoch_seeds, static_argnums=(2,))

  def link_positions(self, order_key, start, k: int):
    """``[k, batch]`` positions in the link loader's seed edges that
    steps ``start .. start + k`` of an epoch train on: step ``g`` takes
    ``g * batch + arange(batch)``, through the epoch's ``keyed_order``
    under ``shuffle=True``. Traced into the chunk (``glt.sample/seeds``)
    and callable on its own: a replay asks the trainer which seed edges
    a step had."""
    import jax
    import jax.numpy as jnp
    batch = self._batch_size
    with jax.named_scope(SCOPE_SAMPLE), jax.named_scope(SCOPE_SEEDS):
      pos = ((start + jnp.arange(k, dtype=jnp.int32))[:, None] * batch +
             jnp.arange(batch, dtype=jnp.int32)[None, :])
      if self._shuffle:
        pos = keyed_order(order_key, len(self.loader.rows), pos)
    return pos

  def _build_chunk_fn(self):
    """The scanned K-step program. Chunk position enters as a DEVICE
    scalar (dynamic_slice start), so every full chunk reuses one
    executable; only the tail length retraces. State and the overflow
    carry are donated — HBM stays flat across chunk dispatches.

    A node job slices its steps' seeds out of the epoch's seed matrix;
    a link job has none — ``seed_mat`` is the epoch's order key, and the
    chunk evaluates the order for its own ``k`` steps
    (:meth:`link_positions`), so an epoch over every edge of a graph
    costs a call the steps it runs."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    sample_collate = self._sample_collate
    train_step = self._train_step   # jit-of-jit: inlined into the scan
    step_keys, stride = self._step_keys, self._key_stride
    link_positions = (self.link_positions
                      if isinstance(self.loader, LinkLoader) else None)

    def scan_epoch_chunk(state, ovf, fargs, feats, id2i, labels,
                         seed_mat, mask_mat, base_key, count0, start, k):
      if link_positions is not None:
        seeds_k = link_positions(seed_mat, start, k)
        masks_k = jnp.ones(seeds_k.shape, bool)
      else:
        seeds_k = lax.dynamic_slice_in_dim(seed_mat, start, k, axis=0)
        masks_k = lax.dynamic_slice_in_dim(mask_mat, start, k, axis=0)
      # the sampler's fold_in stream: global step g -> count0 + g (a
      # typed step draws `stride` counts, one per (hop, edge type))
      if stride == 1:
        counts_k = count0 + start + lax.iota(seeds_k.dtype, k)
      else:
        counts_k = count0 + (start + lax.iota(seeds_k.dtype, k)) * stride

      def body(carry, xs):
        state, ovf = carry
        seeds, smask, count = xs
        key = step_keys(base_key, count)
        # (batch, overflow) and, from a link source, its per-step counts
        batch, overflow, *counts = sample_collate(fargs, feats, id2i,
                                                  labels, seeds, smask, key)
        state, loss, acc = train_step(state, batch)
        return (state, ovf | overflow), (loss, acc, *counts)

      (state, ovf), outs = lax.scan(
          body, (state, ovf), (seeds_k, masks_k, counts_k))
      return (state, ovf, *outs)

    return jax.jit(scan_epoch_chunk, static_argnums=(11,),
                   donate_argnums=(0, 1))

  def _build_concat_fn(self):
    """One program concatenating the per-chunk [K] loss/acc outputs."""
    import jax
    import jax.numpy as jnp

    def epoch_metrics_concat(losses, accs):
      return jnp.concatenate(losses), jnp.concatenate(accs)

    return jax.jit(epoch_metrics_concat)

  # ----------------------------------------------------------------- epoch

  def _epoch_steps(self) -> int:
    # the batcher owns the full-batch/ragged-tail arithmetic — one
    # source of truth keeps the scanned step count equal to the
    # per-step loop's by construction
    return len(self.loader._batcher)

  def run_epoch(self, state, max_steps: Optional[int] = None,
                start_step: int = 0, resume_overflow: bool = False):
    """One scanned epoch. Returns ``(state, losses, accs)`` with losses
    and accs [steps]-shaped device arrays — fetch once, after the epoch.

    The input ``state`` is DONATED to the first chunk dispatch and must
    not be reused; train on the returned state. ``max_steps`` truncates
    the epoch to exactly that many optimizer updates (the permutation is
    still drawn for the full epoch, so truncation never changes which
    seeds later steps would have seen).

    ``start_step`` (a chunk boundary — a multiple of ``chunk_size``)
    resumes THIS epoch mid-flight: the seed matrix is drawn for the
    full epoch as usual and the scan starts at that boundary, so with
    the sampler counter and epoch index restored the remaining chunks
    replay BIT-IDENTICALLY (the recovery/ resume path — callers should
    go through ``recovery.ChunkCheckpointer.resume_epoch``, which also
    restores the counters). ``resume_overflow`` seeds the overflow
    carry with the flag the interrupted prefix had accumulated.
    Returned losses/accs then cover only ``[start_step, steps)``."""
    import jax
    import jax.numpy as jnp

    from ..metrics import flight
    guarded, recompute = self.loader._overflow_epoch_start()
    if recompute:
      raise ValueError(_RECOMPUTE_MSG)
    self.loader._begin_epoch()
    epoch_no = self._epochs
    full_steps = self._epoch_steps()
    steps = full_steps
    truncated = False
    if max_steps is not None and max_steps < steps:
      steps, truncated = max_steps, True
    if start_step:
      if start_step % self.chunk_size != 0:
        raise ValueError(f'start_step={start_step} is not a chunk '
                         f'boundary (chunk_size={self.chunk_size}) — '
                         'resume only at the boundaries checkpoints '
                         'are taken at')
      if not 0 <= start_step < steps:
        raise ValueError(f'start_step={start_step} outside this '
                         f"epoch's {steps} steps")
    # the epoch span is current for the whole program region: chunk
    # spans (and any spans the model hooks open) parent under it. Both
    # brackets open AFTER the step arithmetic (and, on the zero-step
    # path, after the empty-result device work) so nothing between
    # open and close can raise — a flight record opened before the
    # resume-argument raises above would stay permanently open, and an
    # attached span leaked by a prologue exception would mis-parent
    # the thread's spans for the rest of the process
    if steps <= 0:
      # zero-batch epochs still record (the per-step loop writes a
      # steps=0 line) so flight epoch counts line up across drivers
      empty = jnp.zeros((0,), jnp.float32)
      flight_tok = flight.epoch_begin()
      epoch_span = spans.begin('epoch.run', emitter=self._NAME,
                               epoch=epoch_no)
      spans.end(epoch_span, steps=0, completed=True)
      flight.epoch_end(flight_tok, emitter=self._NAME, epoch=epoch_no,
                       steps=0, config=self._flight_config(),
                       extra={'chunk_size': self.chunk_size,
                              'truncated': truncated})
      return state, empty, empty

    flight_tok = flight.epoch_begin()
    epoch_span = spans.begin('epoch.run', emitter=self._NAME,
                             epoch=epoch_no)
    completed = False
    # reset BEFORE the body: a failure in its staging prologue (fused
    # args rebuild, carry device_puts) must read as the resume point,
    # not the previous epoch's stale count — a resume that fails still
    # records the chunk boundary it reached
    self._steps_dispatched = start_step
    try:
      state, losses, accs, ovf = self._run_epoch_body(
          state, steps, full_steps, start_step=start_step,
          resume_overflow=resume_overflow)
      completed = True
      with spans.span('epoch.publish'):
        self._publish_step_counts()
      if guarded:
        # natural epoch end applies overflow_policy; a max_steps
        # break leaves the
        # device-accumulated flag to loader.check_overflow()
        self.loader._ovf_accum = ovf
        if not truncated:
          self.loader._finish_epoch_overflow()
    finally:
      # one JSONL flight record per epoch (metrics/flight.py): pure
      # host counter deltas + wall — written OUTSIDE strict_guards,
      # zero extra dispatches, zero device fetches. A mid-scan failure
      # still records (completed=False), with the un-advanced epoch
      # number the re-run will redraw and the steps the scan actually
      # dispatched (chunk-granular), matching the per-step emitters'
      # delivered-batch semantics
      spans.end(epoch_span,
                steps=(steps if completed else
                       getattr(self, '_steps_dispatched', 0)),
                completed=completed)
      flight.epoch_end(flight_tok, emitter=self._NAME, epoch=epoch_no,
                       steps=(steps if completed else
                              getattr(self, '_steps_dispatched', 0)),
                       completed=completed,
                       config=self._flight_config(),
                       extra={'chunk_size': self.chunk_size,
                              'truncated': truncated,
                              'start_step': start_step})
    return state, losses, accs

  def _run_epoch_body(self, state, steps, full_steps, start_step=0,
                      resume_overflow=False):
    """The epoch program proper: seed draw + scanned chunks. Split out
    so run_epoch owns only the guard/flight bracketing."""
    import jax
    link = isinstance(self.loader, LinkLoader)
    self._step_counts = []   # a failed epoch's counts are not carried on
    # the call's prologue, before any device program: on a profiler
    # timeline the idle gap in front of epoch.seeds is this span's
    with spans.span('epoch.stage'):
      if self._seeds_dev is None and not link:
        self._seeds_dev = jax.device_put(
            np.asarray(self.loader.input_seeds, dtype=np.int32))
      # _epochs advances only on SUCCESS (below, with _call_count): a
      # failed epoch's re-run must redraw the SAME permutation, matching
      # the un-advanced sampler key stream
      perm_key = jax.random.fold_in(self._perm_key, self._epochs)

      # graph arrays re-fetched each epoch: the padded-table reseed in
      # _begin_epoch must reach the chunks (lazy rebuild in _fused_args)
      fargs = self._sample_args()
      base_key = self._sampler._key
      # chunk-position scalars enter as EXPLICIT device_puts: inside the
      # strict_guards region (GLT_STRICT=1: transfer_guard('disallow') +
      # checking_leaks) every implicit host->device transfer — a stray
      # numpy arg, an eager op minting a constant — raises, so the epoch
      # region provably contains nothing but all-device program
      # dispatches
      count0 = jax.device_put(np.int32(self._sampler._call_count + 1))
      # a resume seeds the carry with the interrupted prefix's flag — a
      # pre-crash overflow must still fire the epoch-end policy
      ovf = jax.device_put(np.asarray(bool(resume_overflow)))
    losses, accs = [], []
    start = start_step
    with strict_guards():
      # every host phase between two device programs is a span, and an
      # attached span is also a glt.<name> event on the profiler's
      # clock (metrics/spans.py): a trace of this loop names each
      # device-idle gap by what the host was doing in it. The dispatches
      # are async, so a span's dur is dispatch wall; the layers' device
      # time is read from their glt.* scopes (docs/observability.md)
      if link:
        # no seed matrix: each chunk evaluates the epoch's order for its
        # own steps (link_positions), from the order key alone
        seed_mat, mask_mat = perm_key, None
      else:
        record_dispatch('epoch_seeds')
        with spans.span('epoch.seeds'):
          seed_mat, mask_mat = self._seed_fn(self._seeds_dev, perm_key,
                                             full_steps)
      while start < steps:
        k = min(self.chunk_size, steps - start)
        if self.stage_hook is not None:
          with spans.span('epoch.hook', hook='stage', start=start):
            self.stage_hook(start // self.chunk_size, start, k)
        record_dispatch('scan_chunk')
        with spans.span('epoch.chunk', start=start, k=k):
          state, ovf, loss_k, acc_k, *counts_k = self._chunk_fn(
              state, ovf, fargs, self._feats, self._id2i, self._labels,
              seed_mat, mask_mat, base_key, count0,
              jax.device_put(np.int32(start)), k)
        losses.append(loss_k)
        accs.append(acc_k)
        self._step_counts.extend(counts_k)
        self._steps_dispatched = start + k
        if self.ack_hook is not None:
          # boundary carry for the recovery seam (recovery/checkpoint):
          # valid ONLY inside the hook call — the next chunk dispatch
          # donates state/ovf. Hooks may device_get it (explicit
          # fetches pass the strict transfer guard); they must never
          # fetch implicitly or dispatch programs.
          self._chunk_carry = dict(state=state, ovf=ovf, losses=losses,
                                   accs=accs, steps=steps,
                                   full_steps=full_steps,
                                   start_step=start_step)
          with spans.span('epoch.hook', hook='ack', start=start):
            self.ack_hook(start // self.chunk_size, start, k)
        start += k
      if len(losses) > 1:
        record_dispatch('metrics_concat')
        with spans.span('epoch.concat'):
          losses, accs = self._concat_fn(losses, accs)
      else:
        losses, accs = losses[0], accs[0]
    # keep the host fold_in stream aligned with what the device consumed
    # (checkpoint/resume and any later per-step sampling continue it)
    self._sampler._call_count += steps * self._key_stride
    self._epochs += 1
    return state, losses, accs, ovf

  def _publish_step_counts(self):
    """The batch source's per-step counts into their counters — a link
    epoch's ``link.negatives.{tested,rejected,padded}`` and
    ``link.seeds.unique``, a typed epoch over learned tables'
    ``table.rows_read``: the chunks' ``[k, n]`` scan outputs fetched
    once, after the epoch (no fetch in the loop), as
    ``dist_exchange.rows.hop<h>`` is. Any other epoch has none."""
    from ..utils import trace
    counts, self._step_counts = self._step_counts, []
    if not counts:
      return
    total = np.sum([np.asarray(c).sum(axis=0, dtype=np.int64)
                    for c in counts], axis=0)
    for name, n in zip(self._count_names, total.tolist()):
      # graftlint: allow[metric-registry] link.* and table.rows_read names
      trace.counter_inc(name, int(n))

  def _flight_config(self) -> dict:
    """Static epoch-program configuration, fingerprinted into flight
    records so a postmortem can group epochs by config across runs."""
    return dict(trainer=self._NAME, batch_size=self._batch_size,
                chunk_size=self.chunk_size,
                fanouts=list(self._sampler.num_neighbors),
                shuffle=self._shuffle, drop_last=self._drop_last,
                num_classes=self.num_classes,
                seed=self.loader._batcher.seed)

  # -------------------------------------------------- recovery protocol
  # (recovery/checkpoint.py ChunkCheckpointer — docs/recovery.md)

  def _recovery_config(self) -> dict:
    return _recovery_config_for(self)

  def _recovery_capture(self, carry):
    """(meta_extra, device_arrays_extra) a boundary snapshot must
    carry beyond the train state: the sampler stream position (base
    key + counter — it still holds the EPOCH-START value while the
    epoch is in flight) and, for padded-window sampling, the
    padded-table reseed counters."""
    meta = {'sampler': self._sampler.state_dict()}
    s = self._sampler
    if getattr(s, 'padded_window', None) is not None:
      meta['padded'] = {
          'seed': int(s._padded_seed),
          'epochs_started': int(getattr(self.loader, '_epochs_started',
                                        0))}
    return meta, {}

  def _recovery_load(self, meta, arrays):
    """Rewind this (typically fresh) trainer to the snapshot's epoch:
    sampler stream, epoch index, and — for padded-window sampling —
    the padded-table reseed counters, positioned so run_epoch's own
    ``_begin_epoch`` lands the table on exactly the crashed epoch's
    seed (no refresh for a first epoch, one refresh otherwise)."""
    del arrays   # the local trainer carries no extra device state
    self._sampler.load_state_dict(meta['sampler'])
    self._epochs = int(meta['epoch'])
    pad = meta.get('padded')
    if pad:
      s = self._sampler
      es = int(pad['epochs_started'])
      if es <= 1:
        self.loader._epochs_started = 0
        s._padded_seed = int(pad['seed'])
      else:
        self.loader._epochs_started = es - 1
        s._padded_seed = int(pad['seed']) - 1
      # drop any cached padded table so the resumed epoch rebuilds it
      # from the restored seed
      s._garrs.pop(('padded', id(s._get_graph())), None)

  def _recovery_advance(self, meta):
    """A COMPLETED-epoch snapshot resumes as 'advance past it': the
    stream/epoch counters land where a normal epoch end would leave
    them, and the padded-table counters keep the values captured
    DURING that epoch (the next run_epoch's ``_begin_epoch`` then
    refreshes onto the FOLLOWING epoch's seed, matching the
    uninterrupted multi-epoch stream). No stats restore: a finished
    epoch already published its accumulators before the crash."""
    self._sampler.load_state_dict(meta['sampler'])
    self._sampler._call_count += int(meta['steps']) * self._key_stride
    self._epochs = int(meta['epoch']) + 1
    pad = meta.get('padded')
    if pad:
      s = self._sampler
      self.loader._epochs_started = int(pad['epochs_started'])
      s._padded_seed = int(pad['seed'])
      s._garrs.pop(('padded', id(s._get_graph())), None)


class DistScanTrainer(DistFusedEpochTrainer):
  """Distributed epoch-as-a-program: one epoch of the COLLOCATED mesh
  loop as ``ceil(steps/K) + 2`` dispatches.

  The per-step distributed loop pays >= 2 program dispatches per batch
  (sample program + collate, plus the feature/label gathers and the
  train step) and a host numpy seed slice each step. Here
  the scanned chunk is ONE jitted shard_map program whose ``lax.scan``
  body composes, per shard and per step:

    per-shard seed slice (dynamic_slice into the on-device [steps, B]
    seed matrix) -> fold_in key replay (``split(fold_in(base, count),
    P)[shard]`` — exactly DistNeighborSampler._keys_for, so a
    shuffle=False scanned epoch replays the per-step loop's draws
    BIT-IDENTICALLY) -> the sampler's multi-hop all_to_all exchange
    (_homo_hop_loop / _hetero_engine) -> DistFeature's cached miss-only
    lookup with the [4] stats row in the scan carry (publish_stats()
    still fetches once per epoch) -> label gather -> the pmean'd
    data-parallel train step. The calibrated-caps overflow flag
    (already psum-replicated by the engine) ORs into the carry.

  Collocated-mesh only: remote/server-client topologies run their own
  scanned path (``distributed.RemoteScanTrainer`` — the chunk-staged
  hybrid over server-produced K-batch blocks, docs/remote_scan.md;
  mp-worker loaders keep the per-step loop), and
  ``overflow_policy='recompute'`` is rejected (per-batch host sync).
  On failover/restart the scan carry and cache state are rebuilt —
  failover granularity is the CHUNK, not the batch.

  Args:
    loader: collocated DistNeighborLoader (homo or hetero) with
      feature collection and node labels.
    chunk_size: K steps per scanned dispatch (the tail chunk compiles
      once more at its own length).
    perm_seed: base seed for the ON-DEVICE epoch permutation (default:
      the loader's seed). The host loader's numpy shuffle stream is
      left untouched; shuffle=False epochs replay the host order
      exactly (arange + cyclic tail padding).

  Usage:
      trainer = DistScanTrainer(loader, model, tx, num_classes, K)
      state, losses, accs = trainer.run_epoch(state)
  """

  _NAME = 'DistScanTrainer'

  # chunk-boundary staging hooks — same contract as ScanTrainer's:
  # host-side callables around each chunk dispatch, the attachment
  # point for per-shard staging pipelines (docs/storage.md documents
  # the distributed tier model and its current scope)
  stage_hook = None
  ack_hook = None

  def __init__(self, loader, model, tx, num_classes: int,
               chunk_size: int = 32,
               seed_labels_only: Optional[bool] = None,
               perm_seed: Optional[int] = None):
    import jax
    super().__init__(loader, model, tx, num_classes, seed_labels_only)
    # per chunk, the [P, k, hops] counts of frontier ids each shard sent
    # to other shards: a scan output, summed on the host once an epoch
    # (_publish_exchange_rows empties it)
    self._sent = []
    if chunk_size < 1:
      raise ValueError(f'chunk_size must be >= 1, got {chunk_size}')
    self.chunk_size = int(chunk_size)
    if perm_seed is None:
      perm_seed = loader.seed or 0
    # tag the perm stream off fold_in(2**32 - 1): the sampler's step
    # keys are split(fold_in(PRNGKey(seed), count >= 1), P) on the SAME
    # default seed — the tag sits where no step counter can ever land
    self._perm_key = jax.random.fold_in(jax.random.PRNGKey(perm_seed),
                                        0xFFFFFFFF)
    self._epochs = 0        # folds into the perm key: fresh shuffle/epoch
    self._seeds_dev = None  # input seeds, uploaded once
    self._shard_tree, self._repl_tree, self._sc_body = \
        self._make_sample_collate()
    self._seed_fn = programs.instrument(self._build_seed_fn(),
                                        'dist_epoch_seeds')
    self._chunk_fns = {}    # k (static chunk length) -> program
    self._concat_fn = programs.instrument(self._build_concat_fn(),
                                          'dist_metrics_concat')

  # ------------------------------------------------------------- programs

  def _build_seed_fn(self):
    """ONE program for the epoch prologue: permutation draw + seed
    gather + [P, steps, B] reshape + ragged-tail validity mask.
    Replays DistLoader._index_blocks exactly for shuffle=False: blocks
    are row-major [steps, P, B] slices of the epoch order, and the
    short final block is padded by CYCLING the order (np.resize) with
    the pad slots masked invalid.

    Outputs are committed to the chunk program's [P, ...] mesh sharding
    HERE (out_shardings) — otherwise the matrices land on one device
    and the first chunk dispatch pays a hidden device-to-device
    reshard, which GLT_STRICT's transfer_guard('disallow') rejects."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch = self._batch_size
    nparts = self._nparts
    shuffle = self.loader.shuffle
    sharded = NamedSharding(self.mesh, P(self._axes))

    def epoch_seeds(seeds, key, steps):
      n = seeds.shape[0]
      order = (jax.random.permutation(key, n) if shuffle
               else jnp.arange(n, dtype=jnp.int32))
      total = steps * nparts * batch
      if total <= n:       # drop_last: the permutation's prefix
        ext = order[:total]
        maskf = jnp.ones((total,), bool)
      else:                # ragged tail: cyclic pad, masked invalid
        pad = order[jnp.arange(total - n, dtype=jnp.int32) % n]
        ext = jnp.concatenate([order, pad])
        maskf = jnp.arange(total) < n
      seed_mat = seeds[ext].reshape(steps, nparts, batch)
      mask_mat = maskf.reshape(steps, nparts, batch)
      # leading axis = partition: the chunk program shards on dim 0
      return (seed_mat.transpose(1, 0, 2),
              mask_mat.transpose(1, 0, 2))

    return jax.jit(epoch_seeds, static_argnums=(2,),
                   out_shardings=(sharded, sharded))

  def _chunk_fn_for(self, k: int):
    """The scanned K-step shard_map program (built per static chunk
    length; the chunk position enters as a DEVICE scalar so every full
    chunk reuses one executable). State and the overflow/stats carry
    are donated — HBM stays flat across chunk dispatches."""
    if k in self._chunk_fns:
      return self._chunk_fns[k]
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..utils.compat import shard_map
    ax = self._axes
    mesh = self.mesh
    nparts = self._nparts
    sc_body = self._sc_body
    dp = self._dp_step_body

    def body(shard_tree, repl_tree, stats, params, opt_state, stepc,
             ovf, seed_mat, mask_mat, base_key, count0, start):
      # row tables keep their [1, n, F] axis: dropping it copies the
      # table in front of the loop (DistFeature._shard_body indexes
      # through it)
      views = jax.tree.map(lambda a: a if a.ndim == 3 else a[0],
                           shard_tree)
      stats_rows = jax.tree.map(lambda a: a[0], stats)
      seeds_k = lax.dynamic_slice_in_dim(seed_mat[0], start, k, 0)
      masks_k = lax.dynamic_slice_in_dim(mask_mat[0], start, k, 0)
      # the sampler's fold_in stream: global step g -> count0 + g
      counts_k = count0 + start + lax.iota(jnp.int32, k)
      # this shard's linear partition index, row-major over the axis
      # order — matches the [P, ...] leading-axis sharding and the
      # per-step path's keys[p] selection
      my = jnp.int32(0)
      for a in ax:
        my = my * mesh.shape[a] + lax.axis_index(a)

      def step(carry, xs):
        params, opt_state, stepc, ovf, srows = carry
        seeds, smask, count = xs
        keys = jax.random.split(jax.random.fold_in(base_key, count),
                                nparts)
        batch, overflow, srows, sent = sc_body(
            views, repl_tree, srows, seeds, smask, keys[my])
        state, loss, acc = dp(
            self._train_state_cls(params, opt_state, stepc), batch)
        return (state.params, state.opt_state, state.step,
                ovf | overflow, srows), (loss, acc, sent)

      (params, opt_state, stepc, ovf, srows), (losses, accs, sent) = \
          lax.scan(step, (params, opt_state, stepc, ovf, stats_rows),
                   (seeds_k, masks_k, counts_k))
      return (params, opt_state, stepc, ovf,
              jax.tree.map(lambda a: a[None], srows), losses, accs,
              sent[None])

    sh = jax.tree.map(lambda _: P(ax), self._shard_tree)
    rp = jax.tree.map(lambda _: P(), self._repl_tree)
    stats_spec = (P(ax) if not self.is_hetero
                  else {t: P(ax) for t in self._feat_types})
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(sh, rp, stats_spec, P(), P(), P(), P(), P(ax), P(ax),
                  P(), P(), P()),
        out_specs=(P(), P(), P(), P(), stats_spec, P(), P(), P(ax)),
        check_replication=False)
    # donate the train state + the overflow/stats carries (args 3-6 +
    # 2); the graph/feature tables and seed matrix are reused across
    # chunks and must NOT be donated
    jfn = programs.instrument(jax.jit(fn, donate_argnums=(2, 3, 4, 5, 6)),
                              'dist_scan_chunk')
    self._chunk_fns[k] = jfn
    return jfn

  def _build_concat_fn(self):
    """One program concatenating the per-chunk [K] loss/acc outputs."""
    import jax
    import jax.numpy as jnp

    def epoch_metrics_concat(losses, accs):
      return jnp.concatenate(losses), jnp.concatenate(accs)

    return jax.jit(epoch_metrics_concat)

  # ----------------------------------------------------------------- epoch

  def run_epoch(self, state, max_steps: Optional[int] = None,
                start_step: int = 0, resume_overflow: bool = False):
    """One scanned distributed epoch. Returns ``(state, losses, accs)``
    with losses/accs [steps]-shaped replicated device arrays — fetch
    once, after the epoch.

    The input ``state`` is DONATED to the first chunk dispatch; train
    on the returned state. ``max_steps`` truncates the epoch to exactly
    that many optimizer updates (the permutation is still drawn for the
    full epoch, so truncation never changes which seeds later steps
    would have seen). ``start_step``/``resume_overflow`` resume THIS
    epoch at a chunk boundary — the recovery seam (see
    ``ScanTrainer.run_epoch``; go through ``recovery.
    ChunkCheckpointer.resume_epoch``, which also restores the sampler
    counter, epoch index and feature-cache stats rows)."""
    import jax
    import jax.numpy as jnp

    from ..metrics import flight
    guarded, recompute = self.loader._overflow_epoch_start()
    if recompute:   # unreachable after __init__'s check; kept for parity
      raise ValueError(_RECOMPUTE_MSG)
    epoch_no = self._epochs
    full_steps = len(self.loader)
    steps = full_steps
    truncated = False
    if max_steps is not None and max_steps < steps:
      steps, truncated = max_steps, True
    if start_step:
      if start_step % self.chunk_size != 0:
        raise ValueError(f'start_step={start_step} is not a chunk '
                         f'boundary (chunk_size={self.chunk_size})')
      if not 0 <= start_step < steps:
        raise ValueError(f'start_step={start_step} outside this '
                         f"epoch's {steps} steps")
    # both brackets open after the step arithmetic (and the zero-step
    # path's empty-result device work): every statement between open
    # and close is a try/finally body or a bracket call, so every path
    # provably ends them — see ScanTrainer.run_epoch
    if steps <= 0:
      # mirror the per-step loop's zero-batch epoch (DistLoader.__iter__
      # closes the overflow guard and STILL publishes in its finally):
      # the feature-stats accumulators a prior template iteration left
      # on device must drain this epoch too, or they eventually wrap
      empty = jnp.zeros((0,), jnp.float32)
      flight_tok = flight.epoch_begin()
      epoch_span = spans.begin('epoch.run', emitter=self._NAME,
                               epoch=epoch_no)
      try:
        if guarded and not truncated:
          self.loader._finish_epoch_overflow()
      finally:
        # publish BEFORE the flight record (feature fields must
        # bit-match the freshly published counters) but never at the
        # cost of the record or the attached span: a raising fetch
        # must still end both (a leaked attached span mis-parents
        # every later span on this thread)
        try:
          self.loader._publish_feature_stats()
        finally:
          # zero-batch epochs still record, like the per-step loop's
          # steps=0 line, so flight epoch counts line up across drivers
          spans.end(epoch_span, steps=0, completed=True)
          flight.epoch_end(flight_tok, emitter=self._NAME,
                           epoch=epoch_no, steps=0,
                           config=self._flight_config(),
                           extra={'chunk_size': self.chunk_size,
                                  'truncated': truncated})
      return state, empty, empty

    flight_tok = flight.epoch_begin()
    epoch_span = spans.begin('epoch.run', emitter=self._NAME,
                             epoch=epoch_no)
    completed = False
    # reset BEFORE the body: a failure in its staging prologue (the
    # replicated-carry device_puts, program retraces) must read as the
    # resume point, not the previous epoch's stale count — a resume
    # that fails still records the chunk boundary it reached
    self._steps_dispatched = start_step
    try:
      state, losses, accs, ovf = self._run_epoch_body(
          state, steps, full_steps, start_step=start_step,
          resume_overflow=resume_overflow)
      completed = True
      if guarded:
        # same contract as the local trainers: natural epoch end
        # applies overflow_policy; a max_steps break leaves the flag to
        # loader.check_overflow()
        self.loader._ovf_accum = ovf
        if not truncated:
          self.loader._finish_epoch_overflow()
    finally:
      # also when the epoch fails mid-scan or the overflow guard raises
      # — the per-step loop's finally-publish contract (the accumulator
      # must drain per epoch; a dropped partial-epoch accumulator
      # publishes zeros). Flight record AFTER publish_stats: the
      # feature fields must bit-match the freshly published
      # dist_feature.* counters. Host deltas only — outside
      # strict_guards, zero extra dispatches; a failed epoch records
      # completed=False under the un-advanced epoch number its re-run
      # will redraw. The publish is itself a device fetch that can
      # raise against a broken device — the span and flight record
      # (the postmortem trail for exactly that failure) must still
      # close, so they sit in an inner finally
      try:
        with spans.span('epoch.publish'):
          self.loader._publish_feature_stats()
          self._publish_exchange_rows()
      finally:
        spans.end(epoch_span,
                  steps=(steps if completed else
                         getattr(self, '_steps_dispatched', 0)),
                  completed=completed)
        flight.epoch_end(flight_tok, emitter=self._NAME, epoch=epoch_no,
                         steps=(steps if completed else
                                getattr(self, '_steps_dispatched', 0)),
                         completed=completed,
                         config=self._flight_config(),
                         extra={'chunk_size': self.chunk_size,
                                'truncated': truncated,
                                'start_step': start_step})
    return state, losses, accs

  def _run_epoch_body(self, state, steps, full_steps, start_step=0,
                      resume_overflow=False):
    """The mesh epoch program proper: replicated carry staging + seed
    draw + scanned chunks. Split out so run_epoch owns only the
    guard/publish/flight bracketing."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec
    repl = NamedSharding(self.mesh, PartitionSpec())
    # the call's prologue, before any device program: on a profiler
    # timeline the idle gap in front of epoch.seeds is this span's
    with spans.span('epoch.stage'):
      if self._seeds_dev is None:
        # committed to the mesh (replicated) at upload: the seed program
        # runs on the mesh, and an uncommitted single-device array would
        # be broadcast IMPLICITLY at its first dispatch — a hidden
        # device-to-device transfer GLT_STRICT's transfer guard rejects
        self._seeds_dev = jax.device_put(
            np.asarray(self.loader.input_seeds, dtype=np.int32), repl)
      # _epochs advances only on SUCCESS (below, with _call_count): a
      # failed epoch's re-run must redraw the SAME permutation or the
      # chunk-granularity failover story (docs/failure_model.md) can't
      # reproduce the completed chunks' seed matrix
      perm_key = jax.device_put(
          jax.random.fold_in(self._perm_key, self._epochs), repl)

      base_key = jax.device_put(self._sampler._key, repl)
      stats = ({t: self._feat[t]._stats_dev() for t in self._feat_types}
               if self.is_hetero else self._feat._stats_dev())
      # commit the replicated carry leaves explicitly: a fresh (host /
      # single-device) state and the chunk program's replicated outputs
      # must present the SAME sharding signature, or every epoch's first
      # chunk retraces (sharding is part of the jit cache key). The
      # chunk-position scalars are explicit device_puts too: inside the
      # strict_guards region (GLT_STRICT=1: transfer_guard('disallow') +
      # checking_leaks) any implicit host->device transfer raises, so
      # the epoch region provably dispatches only all-device program
      # args
      count0 = jax.device_put(np.int32(self._sampler._call_count + 1),
                              repl)
      params, opt_state, stepc, ovf = jax.device_put(
          (state.params, state.opt_state, state.step,
           np.asarray(bool(resume_overflow))), repl)

    def stats_back(tree):
      # hand the carried accumulators back to the stores AFTER EVERY
      # chunk (not just at epoch end): each chunk DONATES its stats
      # input, so the store must never be left referencing a deleted
      # buffer — a mid-epoch stats() read, or a later publish after an
      # aborted epoch, would otherwise raise 'Array has been deleted'
      if self.is_hetero:
        for t in self._feat_types:
          self._feat[t]._stats = tree[t]
      else:
        self._feat._stats = tree

    losses, accs = [], []
    start = start_step
    try:
      with strict_guards():
        with spans.span('epoch.seeds'):
          seed_mat, mask_mat = self._epoch_prologue(
              perm_key, full_steps, steps, start_step, base_key, count0)
        while start < steps:
          k = min(self.chunk_size, steps - start)
          if self.stage_hook is not None:
            with spans.span('epoch.hook', hook='stage', start=start):
              self.stage_hook(start // self.chunk_size, start, k)
          with spans.span('epoch.chunk', start=start, k=k):
            (params, opt_state, stepc, ovf, stats, loss_k, acc_k,
             sent_k) = self._dispatch_chunk(
                    start // self.chunk_size, k, stats, params,
                    opt_state, stepc, ovf, seed_mat, mask_mat, base_key,
                    count0, jax.device_put(np.int32(start), repl))
          stats_back(stats)
          losses.append(loss_k)
          accs.append(acc_k)
          self._sent.append(sent_k)
          self._steps_dispatched = start + k
          if self.ack_hook is not None:
            # boundary carry for the recovery seam — valid only inside
            # the hook call (the next chunk dispatch donates the state
            # and stats buffers); see ScanTrainer
            self._chunk_carry = dict(
                state=self._train_state_cls(params, opt_state, stepc),
                ovf=ovf, stats=stats, losses=losses, accs=accs,
                steps=steps, full_steps=full_steps,
                start_step=start_step)
            with spans.span('epoch.hook', hook='ack', start=start):
              self.ack_hook(start // self.chunk_size, start, k)
          start += k
        if len(losses) > 1:
          record_dispatch('dist_metrics_concat')
          with spans.span('epoch.concat'):
            losses, accs = self._concat_fn(losses, accs)
        else:
          losses, accs = losses[0], accs[0]
    except BaseException:
      # the in-flight chunk's donated stats input is gone; drop the
      # partial epoch's counts rather than leave a dead reference
      stats_back({t: None for t in self._feat_types}
                 if self.is_hetero else None)
      raise
    # keep the host fold_in stream aligned with what the device consumed
    # (checkpoint/resume and any later per-step sampling continue it)
    self._sampler._call_count += steps
    self._epochs += 1
    return (self._train_state_cls(params, opt_state, stepc),
            losses, accs, ovf)

  # ------------------------------------------------------ exchange rows

  def _publish_exchange_rows(self):
    """The epoch's per-hop counts of frontier ids shards sent to OTHER
    shards for expansion, into ``dist_exchange.rows.hop<h>``: the
    chunks' small scan outputs fetched once an epoch, beside the feature
    stats (no per-batch host sync). A failed or resumed epoch publishes
    the chunks it ran. Typed epochs count none (an empty row)."""
    from ..utils import trace
    sent, self._sent = self._sent, []
    fetch = lambda x: (
        np.asarray(x) if getattr(x, 'is_fully_addressable', True)
        else np.concatenate([np.asarray(s.data)
                             for s in x.addressable_shards]))
    rows = [fetch(x).sum(axis=(0, 1), dtype=np.int64) for x in sent]
    for h, n in enumerate(np.sum(rows, axis=0).tolist() if rows else ()):
      # graftlint: allow[metric-registry] one counter per hop of the registered dist_exchange.* family
      trace.counter_inc(f'dist_exchange.rows.hop{h}', int(n))

  # ---------------------------------------------- exchange-aware seams
  # The two points where the epoch program touches the feature-storage
  # topology, split out so the OVERSUBSCRIBED distributed trainer
  # (storage/dist_scan.py TieredDistScanTrainer) can fold the
  # miss-exchange replay into the prologue and stage per-chunk slabs
  # without re-owning the guard/publish/flight bracketing above. Both
  # run INSIDE strict_guards: anything host-resident they feed the
  # programs must be an explicit device_put.

  def _epoch_prologue(self, perm_key, full_steps, steps, start_step,
                      base_key, count0):
    """ONE prologue dispatch -> (seed_mat, mask_mat) committed to the
    chunk program's mesh sharding. The base program is the seed
    permutation alone; the tiered override extends it with the id-only
    sampler replay whose fetched row matrix becomes the per-chunk
    miss-exchange program (same dispatch, same budget)."""
    del steps, start_step  # the base prologue needs no plan extent
    record_dispatch('dist_epoch_seeds')
    return self._seed_fn(self._seeds_dev, perm_key, full_steps)

  def _dispatch_chunk(self, c, k, stats, params, opt_state, stepc, ovf,
                      seed_mat, mask_mat, base_key, count0, start_dev):
    """Dispatch chunk ``c`` (k steps). The tiered override uploads the
    chunk's staged exchange slabs (explicit device_puts) and routes
    through its slab-aware program; the outputs contract is shared."""
    del c  # the all-HBM chunk program has no per-chunk staging
    record_dispatch('dist_scan_chunk')
    return self._chunk_fn_for(k)(
        self._shard_tree, self._repl_tree, stats, params, opt_state,
        stepc, ovf, seed_mat, mask_mat, base_key, count0, start_dev)

  def _flight_config(self) -> dict:
    """Static epoch-program configuration for flight-record grouping
    (mesh shape included: a resharded restart is a different config)."""
    return dict(trainer=self._NAME, batch_size=self._batch_size,
                chunk_size=self.chunk_size,
                fanouts=self._sampler.num_neighbors,
                shuffle=self.loader.shuffle,
                num_partitions=self._nparts,
                mesh={a: self.mesh.shape[a] for a in self._axes},
                hetero=self.is_hetero, num_classes=self.num_classes,
                seed=self.loader.seed)

  # -------------------------------------------------- recovery protocol
  # (recovery/checkpoint.py ChunkCheckpointer — docs/recovery.md)

  def _recovery_config(self) -> dict:
    return _recovery_config_for(self)

  def _recovery_capture(self, carry):
    """Beyond the train state: the sampler stream position and the
    feature-cache [P, 4] stats accumulators riding the scan carry —
    restoring them keeps the resumed epoch's ``publish_stats`` EXACT,
    not just its losses."""
    meta = {'sampler': self._sampler.state_dict()}
    stats = carry.get('stats')
    if self.is_hetero:
      dev = {f'stats:{t}': stats[t] for t in self._feat_types}
    else:
      dev = {'stats:': stats}
    return meta, dev

  def _recovery_load(self, meta, arrays):
    """Rewind a (typically fresh) trainer to the snapshot's epoch:
    sampler stream, epoch index, and the stores' stats accumulators
    (committed back to the mesh sharding ``_stats_dev`` uses)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..utils import global_device_put
    self._sampler.load_state_dict(meta['sampler'])
    self._epochs = int(meta['epoch'])
    if arrays:
      shard = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
      if self.is_hetero:
        for t in self._feat_types:
          self._feat[t]._stats = global_device_put(
              np.asarray(arrays[f'stats:{t}'], np.int32), shard)
      else:
        self._feat._stats = global_device_put(
            np.asarray(arrays['stats:'], np.int32), shard)

  def _recovery_advance(self, meta):
    """Completed-epoch snapshot: advance the stream past the epoch.
    The stats accumulators are NOT restored — the finished epoch's
    publish already drained them pre-crash, and restoring would
    double-count them into the next epoch's publish."""
    self._sampler.load_state_dict(meta['sampler'])
    self._sampler._call_count += int(meta['steps'])
    self._epochs = int(meta['epoch']) + 1
