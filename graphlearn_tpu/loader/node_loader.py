"""Node-seed loader base.

TPU-native port of /root/reference/graphlearn_torch/python/loader/node_loader.py.
The reference wraps a torch DataLoader over seed ids and collates each index
batch through the sampler + feature stores. Here seed batching is plain
numpy (shuffle/drop_last), every batch is padded to the static
``batch_size`` so downstream jitted steps compile once, and collation is:
sample -> HBM/host feature gather -> label gather -> Data.
"""
from typing import Optional

import numpy as np

from ..data import Dataset
from ..sampler import BaseSampler, NodeSamplerInput
from .transform import to_data, to_hetero_data


class SeedBatcher:
  """Shuffled, batched iteration over seed indices (the torch DataLoader
  replacement; reference node_loader.py:76)."""

  def __init__(self, num_seeds: int, batch_size: int, shuffle: bool,
               drop_last: bool, seed: Optional[int] = None):
    self.num_seeds = num_seeds
    self.batch_size = batch_size
    self.shuffle = shuffle
    self.drop_last = drop_last
    self.seed = seed   # kept: ScanTrainer derives its device perm key
    self._rng = np.random.default_rng(seed)
    # mid-epoch resume bookkeeping (see state_dict below)
    self._epoch_start_state = self._rng.bit_generator.state
    self._consumed = 0
    self._pending_skip = 0

  def __iter__(self):
    # capture the stream position BEFORE the permutation draw: a
    # mid-epoch snapshot replays this epoch's permutation from here
    self._epoch_start_state = self._rng.bit_generator.state
    self._consumed = 0
    order = (self._rng.permutation(self.num_seeds) if self.shuffle
             else np.arange(self.num_seeds))
    skip, self._pending_skip = self._pending_skip, 0
    if skip >= len(self) > 0:
      # snapshot was taken at the epoch's end: the replayed epoch is
      # already complete — the permutation draw above advanced the
      # stream exactly as the original epoch did, so continue straight
      # into the next epoch. (len == 0 epochs yield nothing and must
      # not recurse.)
      yield from self.__iter__()
      return
    n_full = self.num_seeds // self.batch_size
    for i in range(n_full):
      if i < skip:
        self._consumed = i + 1
        continue
      # count BEFORE yielding: a snapshot taken while the consumer holds
      # batch i must record it as consumed (the trainer checkpoints
      # after finishing the step for the batch it was handed)
      self._consumed = i + 1
      yield order[i * self.batch_size:(i + 1) * self.batch_size]
    rem = self.num_seeds - n_full * self.batch_size
    if rem and not self.drop_last:
      self._consumed = n_full + 1
      yield order[n_full * self.batch_size:]

  def __len__(self):
    n_full = self.num_seeds // self.batch_size
    rem = self.num_seeds - n_full * self.batch_size
    return n_full + (1 if rem and not self.drop_last else 0)

  # -- checkpoint/resume (utils.checkpoint) --------------------------------
  # Mid-epoch granularity: the snapshot carries the PRNG state captured
  # at the CURRENT epoch's start plus how many batches were already
  # yielded. A restored batcher regenerates the identical permutation
  # and fast-forwards past the consumed batches, so training resumes at
  # the exact next batch (not the epoch start); subsequent epochs
  # continue the original stream. (The reference has no checkpointing
  # at all — SURVEY §5.)

  def state_dict(self):
    return {'rng_state': self._epoch_start_state,
            'consumed': int(self._consumed)}

  def load_state_dict(self, state):
    self._rng.bit_generator.state = state['rng_state']
    self._epoch_start_state = state['rng_state']
    self._pending_skip = int(state.get('consumed', 0))
    self._consumed = self._pending_skip


class OverflowGuardMixin:
  """Calibrated-caps overflow guard shared by the local and distributed
  loaders.

  Calibrated frontier_caps (sampler.calibrate) keep exact-dedup batches
  ~5x smaller than worst case, but a batch whose unique frontier exceeds
  a cap is TRUNCATED — quietly biased if nobody looks. The reference can
  never truncate (dynamic shapes), so silent truncation must not be
  reachable here by default either. Every sampled batch carries an
  on-device metadata['overflow'] flag; the loader applies
  ``overflow_policy``:

    'raise' (default) — accumulate the flag ON DEVICE (no host sync in
        the hot loop) and fetch it ONCE at epoch end; raise if any batch
        truncated. Loud, zero dispatch-pipeline cost.
    'warn'      — same, warnings.warn instead of raising.
    'recompute' — check each batch's flag on the host and recompute
        offenders at FULL capacities with the SAME PRNG key (the
        untruncated version of the identical draw — exact by
        construction). Costs one device->host sync per batch: correct
        unconditionally, so benchmarks opt into 'raise'/'off'
        explicitly.
    'off'       — round-3 behavior (truncation only visible via
        calibrate.check_no_overflow).
  """

  # defaults for subclasses that skip __init__ (guard inactive)
  overflow_policy = 'off'
  overflow_recomputes = 0
  _ovf_accum = None
  _full_sampler = None

  _OVERFLOW_POLICIES = ('raise', 'warn', 'recompute', 'off')

  def _init_overflow_policy(self, policy: str):
    if policy not in self._OVERFLOW_POLICIES:
      raise ValueError(f'overflow_policy {policy!r} not in '
                       f'{self._OVERFLOW_POLICIES}')
    if policy == 'recompute' and \
        getattr(getattr(self, 'sampler', None), 'is_hetero', False):
      # hetero sampling draws (hop, etype) keys from the sampler's
      # internal stream — no replayable per-batch key exists, so a
      # full-caps recompute could not reproduce the truncated draw
      # graftlint: allow[hetero-gate] no replayable hetero batch key
      raise ValueError(
          "overflow_policy='recompute' is homogeneous-only (hetero "
          'batches have no replayable per-batch key); use '
          "'raise'/'warn', or recalibrate with more slack")
    self.overflow_policy = policy
    self.overflow_recomputes = 0   # total full-caps replays ('recompute')
    self._ovf_accum = None         # on-device accumulated flag
    self._full_sampler = None      # lazy uncapped clone

  def _overflow_guarded(self) -> bool:
    return getattr(self.sampler, 'clamped_exact', False) and \
        self.overflow_policy != 'off'

  def _overflow_epoch_start(self):
    """(guarded, recompute) for this epoch. Also DROPS any flag
    accumulated by a previous, early-exited epoch — a stale flag would
    otherwise make the next clean epoch raise (an early break already
    forfeited that epoch's verdict; it must not taint this one)."""
    self._ovf_accum = None
    guarded = self._overflow_guarded()
    return guarded, guarded and self.overflow_policy == 'recompute'

  def _accumulate_overflow(self, out):
    import jax.numpy as jnp
    flag = out.metadata.get('overflow')
    if flag is None:
      return
    flag = jnp.any(flag)
    self._ovf_accum = (flag if self._ovf_accum is None
                       else jnp.logical_or(self._ovf_accum, flag))

  def _batch_overflowed(self, out) -> bool:
    flag = out.metadata.get('overflow')
    return flag is not None and bool(np.any(np.asarray(flag)))

  def _replay_sampler(self):
    if self._full_sampler is None:
      self._full_sampler = self.sampler.uncapped_clone()
    return self._full_sampler

  def check_overflow(self) -> bool:
    """True iff any batch sampled SINCE the current epoch started has
    tripped the calibrated-caps overflow flag (one device fetch). For
    consumers that exit an epoch early (eval loops with a batch cap,
    early stopping): the automatic epoch-end check only runs when the
    iterator is exhausted, so call this after an early break to keep the
    no-truncation claim honest."""
    if self._ovf_accum is None:
      return False
    return bool(np.asarray(self._ovf_accum))

  def _finish_epoch_overflow(self):
    if self._ovf_accum is None:
      return
    flag, self._ovf_accum = self._ovf_accum, None
    if bool(np.asarray(flag)):
      msg = (
          'calibrated frontier_caps overflowed this epoch: at least one '
          'batch was truncated (quietly biased). Re-calibrate with more '
          'slack (sampler.calibrate.estimate_frontier_caps), or pass '
          "overflow_policy='recompute' to replay offending batches at "
          'full capacities (exact, one host sync per batch).')
      if self.overflow_policy == 'warn':
        import warnings
        warnings.warn(msg, stacklevel=2)
      else:
        raise RuntimeError(msg)


class NodeLoader(OverflowGuardMixin):
  """Sample-and-collate loader over seed nodes
  (reference: loader/node_loader.py:27-113)."""

  seed_labels_only = False   # subclasses that skip __init__ inherit this

  def __init__(self, data: Dataset, node_sampler: BaseSampler,
               input_nodes, batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, with_edge: bool = False,
               collect_features: bool = True, to_device=None,
               seed: Optional[int] = None,
               seed_labels_only: bool = False,
               overflow_policy: str = 'raise'):
    self.data = data
    self.sampler = node_sampler
    # seed_labels_only: gather y for the seed block only (supervision
    # uses seed slots; skips a full-capacity random gather — PERF.md)
    self.seed_labels_only = seed_labels_only
    if isinstance(input_nodes, tuple):
      self.input_type, self.input_seeds = input_nodes
    else:
      self.input_type, self.input_seeds = None, input_nodes
    self.input_seeds = np.asarray(self.input_seeds).reshape(-1)
    self.batch_size = batch_size
    self.collect_features = collect_features
    self.to_device = to_device
    self._init_overflow_policy(overflow_policy)
    self._batcher = SeedBatcher(len(self.input_seeds), batch_size, shuffle,
                                drop_last, seed)
    del with_edge  # carried by the sampler

  def __len__(self):
    return len(self._batcher)

  def state_dict(self):
    """Resumable iteration state (MID-EPOCH granularity): the seed
    shuffle stream + position within the current epoch's permutation,
    plus the sampler's PRNG state — a restored run resumes at the exact
    next batch and replays precisely what the uninterrupted run would
    have produced (SeedBatcher.state_dict)."""
    state = self._batcher.state_dict()
    state['sampler'] = self.sampler.state_dict()
    return state

  def load_state_dict(self, state):
    self._batcher.load_state_dict(state)
    if 'sampler' in state:
      self.sampler.load_state_dict(state['sampler'])

  def _begin_epoch(self):
    """Per-epoch padded-table reseed: rows with deg > window expose a
    fresh random window-subset each epoch, de-biasing the truncation
    (ops.build_padded_adjacency; no-op for non-padded samplers). The
    single counter lives here so every epoch driver — __iter__ and
    the scanned trainers' run_epoch — shares one view of how many
    epochs this loader has run."""
    if getattr(self.sampler, 'padded_window', None) is not None:
      if getattr(self, '_epochs_started', 0) > 0:
        self.sampler.refresh_padded_table()
      self._epochs_started = getattr(self, '_epochs_started', 0) + 1

  def __iter__(self):
    from ..metrics import flight, spans
    self._begin_epoch()
    # overflow-policy resolve BEFORE the flight bracket opens: a config
    # error raising here must not leave a permanently-open record
    guarded, recompute = self._overflow_epoch_start()
    tok = flight.epoch_begin()
    steps, completed = 0, False
    try:
      for i, idx in enumerate(self._batcher):
        # the loader's host work for one batch (glt.loader.batch on a
        # profiler timeline); closed before the yield, so the
        # consumer's spans never parent under a suspended generator's
        with spans.span('loader.batch', step=i):
          seeds = self.input_seeds[idx]
          inp = NodeSamplerInput(seeds, self.input_type)
          if recompute:
            key = self.sampler._next_key()
            out = self.sampler.sample_from_nodes(
                inp, batch_cap=self.batch_size, key=key)
            if self._batch_overflowed(out):
              self.overflow_recomputes += 1
              out = self._replay_sampler().sample_from_nodes(
                  inp, batch_cap=self.batch_size, key=key)
          else:
            out = self.sampler.sample_from_nodes(
                inp, batch_cap=self.batch_size)
            if guarded:
              self._accumulate_overflow(out)
          batch = self._collate_fn(out)
        yield batch
        steps += 1
      completed = True
      if guarded and not recompute:
        self._finish_epoch_overflow()
    finally:
      # one flight record per per-step epoch (metrics/flight.py) —
      # host-side counter deltas only, nothing dispatched or fetched
      flight.end_for(
          self, tok, steps=steps, completed=completed,
          config=dict(loader=type(self).__name__,
                      batch_size=self.batch_size,
                      shuffle=self._batcher.shuffle,
                      drop_last=self._batcher.drop_last,
                      seed=self._batcher.seed,
                      num_neighbors=getattr(self.sampler,
                                            'num_neighbors', None)))

  # -- collate (reference: node_loader.py:85-113) --------------------------
  #
  # Collation runs as ONE jitted dispatch (ops.collate_batch) whose array
  # inputs are all arguments: the loader must never run eager ops on the
  # sampler's still-pending outputs, and never fetch them to host
  # (PERF.md dispatch rules). The reference gathers on the host driver
  # instead (node_loader.py:85-113) — that shape would serialize here.

  def _label_table(self, ntype=None):
    """Device-resident label table, cached (host labels uploaded once)."""
    import jax.numpy as jnp
    if not hasattr(self, '_labels_dev'):
      self._labels_dev = {}
    key = ntype
    if key not in self._labels_dev:
      labels = (self.data.get_node_label(ntype) if ntype is not None
                else self.data.node_labels)
      self._labels_dev[key] = (None if labels is None
                               else jnp.asarray(np.asarray(labels)))
    return self._labels_dev[key]

  def _collate_fn(self, out):
    from .. import ops
    if getattr(self.sampler, 'is_hetero', False):
      x = y = None
      if self.collect_features and self.data.node_features is not None:
        x = {}
        for t, buf in out.node.items():
          store = self.data.get_node_feature(t)
          if store is not None:
            dt = store.device_table()
            if dt is not None:
              x[t] = ops.gather_rows(dt[0], dt[1], buf)
            else:  # host/mixed store: UnifiedTensor mixed path
              x[t] = store[buf]
      if self.data.node_labels is not None:
        y = {}
        for t, buf in out.node.items():
          labels = self._label_table(t)
          if labels is None:
            continue
          if self.seed_labels_only:
            # supervision reads seed slots only, and seeds lead the
            # INPUT type's buffer; other types carry no seed block.
            # Slice by the ENGINE's actual seed cap (out.batch carries
            # the padded seed block) — the hetero engine rounds seed
            # caps up, so batch_size alone could misalign labels
            if t != out.input_type:
              continue
            cap = (out.batch[t].shape[0]
                   if out.batch is not None and t in out.batch
                   else self.batch_size)
            buf = buf[:cap]
          y[t] = ops.gather_rows(labels, None, buf)
      return to_hetero_data(out, x, y)

    feats = id2i = None
    if self.collect_features and self.data.node_features is not None:
      dt = self.data.node_features.device_table()
      if dt is not None:
        feats, id2i = dt
    efeats = None
    if out.edge is not None and self.data.edge_features is not None:
      edt = self.data.edge_features.device_table()
      if edt is not None:
        efeats = edt[0]
    from ..utils.trace import record_dispatch
    record_dispatch('collate')
    res = ops.collate_batch(out.node, out.num_nodes, out.row, out.col,
                            feats, id2i, self._label_table(), efeats,
                            out.edge,
                            label_cap=(self.batch_size
                                       if self.seed_labels_only else None))
    x = res['x']
    if x is None and self.collect_features and \
        self.data.node_features is not None:
      # host/mixed feature store: fall back to the UnifiedTensor path
      x = self.data.node_features[out.node]
    ef = res['edge_attr']
    if ef is None and out.edge is not None and \
        self.data.edge_features is not None:
      ef = self.data.edge_features[out.edge]
    data = to_data(out, x, res['y'], ef,
                   node_mask=res['node_mask'],
                   edge_index=res['edge_index'])
    return data
