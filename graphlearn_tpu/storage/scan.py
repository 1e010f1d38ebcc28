"""TieredScanTrainer: the scanned epoch over an out-of-core feature store.

``loader.ScanTrainer`` requires the whole [N, F] feature table in HBM;
this trainer runs the SAME epoch-as-a-program over a
``storage.TieredFeature`` whose table spans HBM -> host RAM -> disk:

* **Prologue plan, one dispatch, for the steps the call runs.** The
  epoch-seeds program is extended with an id-only replay of the sampler
  (``glt.plan``) over steps ``[start_step, steps)`` of THIS call — not
  over the epoch's ``full_steps``: a ``run_epoch(max_steps=m)`` call
  replays, fetches and deduplicates O(m) steps — under the same
  ``fold_in(base_key, count)`` keys the chunk programs will derive (so
  the draws are bit-identical by the PR 1/4 replay contracts). It emits
  one ``[k, node_cap]`` STORAGE-ROW block per chunk beside the seed
  matrix, and the call's two sums (valid node slots, those the hot
  prefix answers) — still ONE ``epoch_seeds`` dispatch, so the epoch
  budget stays ``ceil(steps/K) + 2``. The dispatch thread fetches
  nothing: each block is fetched (explicit ``jax.device_get``) and
  turned into the chunk's sorted miss set (``planner.chunk_misses``: the
  hot rows dropped, then a sort of the misses) by the STAGING WORKER, beside the
  gather it feeds — 4 B x k x node_cap a chunk.
* **Chunk-boundary staging.** While chunk ``c`` trains on device, the
  bounded staging worker (storage/staging.py) gathers chunk ``c+1``'s
  warm/disk rows into a pow2-padded host slab; at the boundary the
  dispatch thread device_puts the slab (explicit — the strict_guards
  region stays transfer-clean) and dispatches the chunk. Slabs are
  acked (freed) as soon as their chunk is dispatched.
* **In-program tiered gather.** The chunk program's feature gather
  (``glt.collate/tier/{hot,lookup,rows}``) is
  hot-prefix ``take`` + slab ``searchsorted`` — every non-hot row a
  chunk touches is in its slab by construction (the plan is exact), so
  losses are BIT-IDENTICAL to the all-HBM ScanTrainer. The search runs
  over the slots that need it: a node buffer of 2,048 slots or more
  compacts its valid non-hot slots and searches them tile by tile
  (``glt.collate/tier/lookup/.../tile``, ``ceil(misses / tile)`` tiles by
  the draw's rule ``ops.neighbor.draw_tile_rows``); hits and pads never
  walk the search's rounds. Staging shapes
  are pow2-capped: one executable per (chunk length, slab cap) pair.
* **Degradation, never corruption.** A failed/slow staging worker
  degrades to a synchronous gather of the same planned rows
  (``storage.prefetch_miss``); the chaos suite completes the epoch
  bit-identically with a ``storage.stage`` fault armed.

Sampling runs twice per call (once id-only in the plan, once in the
chunks) — the price of an exact plan with zero extra dispatches. On the
chip (``sage-papers-tiered.tiered-scan-exact``, PERF.md section 5: the
builder's runs of PR 41) the plan, the wait for the first slab and the
uploads are read as ``tier_plan_ms`` and ``tier_host_gap_ms``.
"""
import functools
from typing import Optional

import numpy as np

from ..loader.node_loader import NodeLoader
from ..loader.pipeline import refuse_link
from ..loader.scan_epoch import ScanTrainer
from ..metrics import spans
from ..metrics.registry_names import (SCOPE_COLLATE, SCOPE_HOT, SCOPE_LOOKUP,
                                      SCOPE_PLAN, SCOPE_ROWS, SCOPE_TIER,
                                      SCOPE_TILE)
from ..utils.strict import strict_guards
from ..utils.trace import record_dispatch
from . import planner
from .staging import INT32_MAX, ChunkStager
from .tiered import TieredFeature


def _block_misses(block, hot_rows: int) -> np.ndarray:
  """One chunk's sorted miss set from its ``[k, node_cap]`` device block
  of storage rows: the block's one fetch, then ``planner.chunk_misses``.
  Run by the staging worker (``ChunkStager._planned_rows``)."""
  import jax
  return planner.chunk_misses(jax.device_get(block), hot_rows)


def bounded_slab_search(slab_ids, ridx, miss, tile: int):
  """The slab membership search over the slots that need it: ``(code
  [cap], tiles)`` where ``code`` is a ``miss`` slot's position in the
  slab, -1 where the slab does not hold its row and on every other slot,
  and ``tiles`` is the int32 number of tiles searched, ``ceil(n_miss /
  tile)``.

  The misses' storage rows are compacted to a prefix of a ``[cap]`` query
  buffer (a rank by prefix sum and one ``mode='drop'`` scatter,
  ``INT32_MAX`` behind them) and searched ``tile`` queries at a time by a
  loop that runs only the tiles beginning below the last miss; the last
  tile is clamped to end at the cap, so it may search queries of the one
  before again, to the same answers. One gather by rank brings the
  answers back to slot order."""
  import jax
  import jax.numpy as jnp
  from ..ops.unique import searchsorted_membership
  cap = ridx.shape[0]
  assert 0 < tile <= cap, (tile, cap)
  rank = jnp.cumsum(miss, dtype=jnp.int32) - 1
  queries = jnp.full((cap,), INT32_MAX, jnp.int32).at[
      jnp.where(miss, rank, cap)].set(ridx, mode='drop')
  tiles = (rank[-1] + tile) // tile

  def body(i, code):
    with jax.named_scope(SCOPE_TILE):
      lo = jnp.minimum(i * tile, cap - tile)
      found, pos = searchsorted_membership(
          slab_ids, jax.lax.dynamic_slice(queries, (lo,), (tile,)))
      return jax.lax.dynamic_update_slice(
          code, jnp.where(found, pos, -1), (lo,))

  code = jax.lax.fori_loop(0, tiles, body,
                           jnp.full((cap,), -1, jnp.int32))
  return jnp.where(miss, code[jnp.maximum(rank, 0)], -1), tiles


def tiered_gather(hot, slab_ids, slab, id2i, node):
  """Traced three-way feature gather: node-id buffer -> rows from the
  HBM hot prefix or the chunk's staged slab. Mirrors
  ``ops.collate_batch``'s clamp exactly (pad slots -> node id 0), so a
  tiered batch is byte-identical to the all-HBM gather. Rows in neither
  (an impossible case under an exact plan) read as zeros rather than
  garbage. Under ``glt.collate/tier``: ``hot`` (the prefix gather),
  ``lookup`` (the id2index remap and the slab membership search) and
  ``rows`` (the slab row gather and the select).

  Only a valid slot the hot prefix does not answer can change what the
  search decides, so a node buffer wide enough to tile
  (``ops.neighbor.draw_tile_rows`` of its cap, the draw's one rule) is
  searched through :func:`bounded_slab_search`, its tiles named
  ``lookup/.../tile``; every pad is node 0's slot and takes ONE scalar
  search's answer. A narrower buffer is searched in one piece."""
  import jax
  import jax.numpy as jnp
  from ..ops.neighbor import draw_tile_rows
  from ..ops.unique import searchsorted_membership
  with jax.named_scope(SCOPE_COLLATE), jax.named_scope(SCOPE_TIER):
    h = hot.shape[0]
    with jax.named_scope(SCOPE_LOOKUP):
      remap = lambda ids: (id2i[ids] if id2i is not None
                           else ids).astype(jnp.int32)
      ridx = remap(jnp.maximum(node, 0))
      tile = draw_tile_rows(node.shape[0])
      if not tile:
        in_slab, pos = searchsorted_membership(slab_ids, ridx)
      else:
        valid = node >= 0
        code, _ = bounded_slab_search(slab_ids, ridx,
                                      valid & (ridx >= h), tile)
        pad_found, pad_pos = searchsorted_membership(
            slab_ids, remap(jnp.zeros((), node.dtype)))
        code = jnp.where(valid, code, jnp.where(pad_found, pad_pos, -1))
        pos, in_slab = jnp.maximum(code, 0), code >= 0
    with jax.named_scope(SCOPE_HOT):
      hot_rows = hot[jnp.clip(ridx, 0, h - 1)]
    with jax.named_scope(SCOPE_ROWS):
      return jnp.where((ridx < h)[:, None], hot_rows,
                       jnp.where(in_slab[:, None], slab[pos], 0))


class TieredScanTrainer(ScanTrainer):
  """ScanTrainer over a TieredFeature (HBM hot prefix + host warm tier
  + disk cold tier), with the epoch prefetch plan fused into the
  prologue and chunk-boundary staging (module docstring).

  Args (beyond ScanTrainer's):
    max_ahead: staged chunks in flight (2 = double buffer).
    stage_timeout_s: how long a chunk boundary waits for its slab
      before degrading to a synchronous read.
  """

  _NAME = 'TieredScanTrainer'

  def __init__(self, loader: NodeLoader, model, tx, num_classes: int,
               chunk_size: Optional[int] = None,
               seed_labels_only: Optional[bool] = None,
               perm_seed: Optional[int] = None, max_ahead: int = 2,
               stage_timeout_s: float = 30.0, config=None):
    refuse_link(loader, self._NAME)
    store = loader.data.node_features
    if not isinstance(store, TieredFeature):
      raise ValueError(
          f'{self._NAME} drives a storage.TieredFeature store, got '
          f'{type(store).__name__}; use loader.ScanTrainer for all-HBM '
          'Feature tables')
    self._store = store
    # config= takes a tune artifact (docs/tuning.md): fingerprint-
    # validated in ScanTrainer.__init__, supplies the tuned chunk K
    super().__init__(loader, model, tx, num_classes, chunk_size,
                     seed_labels_only, perm_seed, config=config)
    self._stager = ChunkStager(store, max_ahead=max_ahead,
                               timeout_s=stage_timeout_s)
    self.last_plan = None   # EpochPlan of the most recent epoch

  # ------------------------------------------------------ trainer hooks

  def _resolve_feature_tables(self, loader):
    # the device table is the HOT PREFIX only; the id2index remap is
    # shared with the all-HBM path (scan_tables validates hot_rows >= 1
    # so the collate clamp lands on resident rows)
    return self._store.scan_tables()

  def _make_sample_collate_body(self):
    from .. import ops
    sample_fn, label_cap = self._sample_fn, self._label_cap

    def _sample_collate(fargs, feats, id2i, labels, seeds, smask, key):
      hot, slab_ids, slab = feats
      res = sample_fn(*fargs, seeds, smask, key)
      col = ops.collate_batch(res['node'], res['num_nodes'], res['row'],
                              res['col'], None, None, labels, None,
                              None, label_cap=label_cap)
      x = tiered_gather(hot, slab_ids, slab, id2i, res['node'])
      batch = dict(x=x, edge_index=col['edge_index'],
                   edge_mask=res['edge_mask'], y=col['y'],
                   num_seed_nodes=res['num_sampled_nodes'][0])
      return batch, res['overflow']

    return _sample_collate

  def _build_seed_fn(self):
    """The prologue PLAN program: the base seed/permutation program
    (the whole epoch's ``[full_steps, batch]`` seed matrix: what the
    chunks slice is what the all-HBM trainer's chunks slice) and, under
    ``glt.plan``, an id-only sampler replay over steps ``[start,
    steps)`` — the steps this call runs — emitting per chunk its
    ``[k, node_cap]`` block of storage rows, and ``[lookups, hot
    hits]`` of the call: one dispatch. ``full_steps``, ``start`` and
    ``steps`` are static: one executable per call shape."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    base_seeds = super()._build_seed_fn()
    sample_fn = self._sample_fn
    has_id2i = self._id2i is not None
    hot_rows, chunk = self._store.hot_rows, self.chunk_size

    def epoch_seeds(fargs, id2i, seeds, key, base_key, count0,
                    full_steps, start, steps):
      seed_mat, mask_mat = base_seeds(seeds, key, full_steps)
      with jax.named_scope(SCOPE_PLAN):
        counts = count0 + start + lax.iota(jnp.int32, steps - start)

        def step_rows(carry, xs):
          seeds_s, mask_s, count = xs
          k = jax.random.fold_in(base_key, count)
          node = sample_fn(*fargs, seeds_s, mask_s, k)['node']
          safe = jnp.maximum(node, 0)
          ridx = (id2i[safe] if has_id2i else safe).astype(jnp.int32)
          valid = node >= 0
          seen = jnp.stack([valid.sum(dtype=jnp.int32),
                            (valid & (ridx < hot_rows)).sum(
                                dtype=jnp.int32)])
          return carry + seen, ridx

        seen, rows_mat = lax.scan(
            step_rows, jnp.zeros((2,), jnp.int32),
            (seed_mat[start:steps], mask_mat[start:steps], counts))
        blocks = tuple(rows_mat[a:a + chunk]
                       for a in range(0, steps - start, chunk))
      return seed_mat, mask_mat, blocks, seen

    return jax.jit(epoch_seeds, static_argnums=(6, 7, 8))

  # ------------------------------------------------------------- epoch

  def _run_epoch_body(self, state, steps, full_steps, start_step=0,
                      resume_overflow=False):
    """The tiered epoch program: fused plan prologue (one dispatch; the
    staging worker fetches each chunk's block) + staged chunk loop.
    Budget: 1 epoch_seeds + ceil(steps/K) scan_chunk + 1 metrics_concat
    = ceil(steps/K) + 2 — unchanged from the all-HBM trainer. The plan
    covers steps ``[start_step, steps)``: what a call costs follows the
    steps it runs, not the epoch's length. A mid-epoch resume
    (``start_step`` — recovery/checkpoint.py) draws the SAME seed
    matrix (the permutation and sampler streams replay exactly), plans
    from the resume chunk on and begins staging there; consumed chunks
    are neither planned nor staged again."""
    import jax
    with spans.span('epoch.stage'):
      if self._seeds_dev is None:
        self._seeds_dev = jax.device_put(
            np.asarray(self.loader.input_seeds, dtype=np.int32))
      perm_key = jax.random.fold_in(self._perm_key, self._epochs)
      fargs = self._sampler._fused_args()
      base_key = self._sampler._key
      count0 = jax.device_put(np.int32(self._sampler._call_count + 1))
      ovf = jax.device_put(np.asarray(bool(resume_overflow)))
    losses, accs = [], []
    start = start_step
    hot = self._feats
    store = self._store
    slab_rows = 0
    with strict_guards():
      record_dispatch('epoch_seeds')
      with spans.span('epoch.plan', steps=steps - start_step):
        seed_mat, mask_mat, blocks, seen = self._seed_fn(
            fargs, self._id2i, self._seeds_dev, perm_key, base_key,
            count0, full_steps, start_step, steps)
        # a chunk's miss set is made where it is needed, by whoever
        # asks first — the staging worker: an explicit device_get of the
        # chunk's block (strict_guards rejects implicit transfers only)
        # and the dedup of its misses, off the dispatch thread
        first = start_step // self.chunk_size
        plan = planner.EpochPlan(
            chunk_size=self.chunk_size, hot_rows=store.hot_rows,
            warm_rows=store.warm_rows,
            chunk_rows=[np.zeros((0,), np.int64)] * first + [
                functools.partial(_block_misses, b, store.hot_rows)
                for b in blocks])
        del blocks
        self.last_plan = plan
        self._stager.begin_epoch(plan.thunks(), start_chunk=first)
      while start < steps:
        k = min(self.chunk_size, steps - start)
        c = start // self.chunk_size
        if self.stage_hook is not None:
          with spans.span('epoch.hook', hook='stage', start=start):
            self.stage_hook(c, start, k)
        with spans.span('epoch.stage_wait', chunk=c):
          slab_ids_np, slab_np = self._stager.take(c)
        with spans.span('epoch.upload', chunk=c, bytes=slab_np.nbytes):
          slab_ids = jax.device_put(slab_ids_np)
          slab = jax.device_put(slab_np)
        slab_rows += int(slab_np.shape[0])
        record_dispatch('scan_chunk')
        with spans.span('epoch.chunk', start=start, k=k):
          state, ovf, loss_k, acc_k = self._chunk_fn(
              state, ovf, fargs, (hot, slab_ids, slab), self._id2i,
              self._labels, seed_mat, mask_mat, base_key, count0,
              jax.device_put(np.int32(start)), k)
        # the device_put above copied the slab: free its ring slot and
        # let the worker pull the next chunk forward
        self._stager.ack(c)
        del slab_ids_np, slab_np, slab_ids, slab
        losses.append(loss_k)
        accs.append(acc_k)
        self._steps_dispatched = start + k
        if self.ack_hook is not None:
          # the generic chunk-boundary seam (recovery/checkpoint.py
          # rides it) — same carry contract as ScanTrainer
          self._chunk_carry = dict(state=state, ovf=ovf, losses=losses,
                                   accs=accs, steps=steps,
                                   full_steps=full_steps,
                                   start_step=start_step)
          with spans.span('epoch.hook', hook='ack', start=start):
            self.ack_hook(c, start, k)
        start += k
      if len(losses) > 1:
        record_dispatch('metrics_concat')
        with spans.span('epoch.concat'):
          losses, accs = self._concat_fn(losses, accs)
      else:
        losses, accs = losses[0], accs[0]
    with spans.span('epoch.publish'):
      self._publish_tier_counts(plan, seen, slab_rows)
    self._sampler._call_count += steps
    self._epochs += 1
    return state, losses, accs, ovf

  def _publish_tier_counts(self, plan, seen, slab_rows: int):
    """``storage.lookups`` / ``.hot_hits`` (the plan program's two sums,
    fetched here, once a call) and ``storage.planned_rows`` /
    ``.slab_cap_rows`` (the plan the host already holds; the rows of the
    slabs the loop uploaded): what the call looked up, what the hot
    prefix answered, what was staged and what the padded slabs carried
    for it."""
    import jax

    from .. import metrics
    lookups, hits = (int(v) for v in jax.device_get(seen))
    metrics.inc('storage.lookups', lookups)
    metrics.inc('storage.hot_hits', hits)
    metrics.inc('storage.planned_rows', plan.stats()['planned_rows'])
    metrics.inc('storage.slab_cap_rows', slab_rows)

  def _flight_config(self) -> dict:
    cfg = super()._flight_config()
    cfg.update(hot_rows=self._store.hot_rows,
               warm_rows=self._store.warm_rows,
               disk_rows=self._store.disk_rows)
    return cfg

  def _recovery_capture(self, carry):
    """ScanTrainer's capture plus the staging-ring watermarks — a
    postmortem can see how deep the prefetch pipeline was at the
    boundary (resume re-plans and re-stages; the watermarks are
    diagnostic, not replayed state)."""
    meta, dev = super()._recovery_capture(carry)
    meta['staging'] = self._stager.watermarks()
    return meta, dev

  def close(self):
    """Stop the staging worker thread."""
    self._stager.close()


# keep the module's int sentinel importable next to the trainer (the
# slab pad id tests assert against)
__all__ = ['TieredScanTrainer', 'tiered_gather', 'INT32_MAX']
