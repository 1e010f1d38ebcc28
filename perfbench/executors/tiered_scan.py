"""Executor ``tiered_scan``: the epoch as a program over a feature table
that does not fit the chip. The window is whole
``storage.TieredScanTrainer.run_epoch(state, max_steps=steps_per_call)``
calls back to back, each ended by ``block_until_ready`` on its losses: a
call plans the steps it runs (one dispatch), then per chunk waits for the
staged slab, uploads it and dispatches the chunk, while the staging worker
fetches, deduplicates and gathers the next chunk's rows from host memory.

The contract is ``scan``'s (``executors/scan.py``): ONE trainer; the first
call is the window's own call, with an ``ack_hook`` that copies the train
state at the first chunk boundary; the same trainer goes on into the
window. What this executor adds to the first call: it keeps the slab the
trainer's own stager handed over for chunk 0 (host arrays, as
``ChunkStager.take`` returned them).

The replay samples the first chunk's batches again under the first call's
keys, as ``scan`` does, and gathers the validated batches' rows through the
PROGRAM's ``tiered_gather`` from the store's hot prefix and THAT slab —
never through a gather of the executor's own: a row the plan missed, a slab
row that is not the table's, a hot prefix filled wrong all come out as
``bad_rows`` against the generator. ``batches[0]['slab_ids']`` carries the
slab's ids for ``unplanned_rows``.
"""
import numpy as np

from perfbench.executors import run_window, scan


class Executor(scan.Executor):
  """``scan``'s call, traced slice and counts, inherited, over a
  ``TieredScanTrainer``."""

  def __init__(self, cell, traffic, seed, model_dtype=None):
    from graphlearn_tpu.storage import TieredScanTrainer
    self.cell, self.traffic = cell, traffic
    self.loader = cell.make_loader(seed)
    self.model = cell.make_model(model_dtype)
    self.state, tx, self.params0 = cell.make_state(self.model, seed)
    self.trainer = TieredScanTrainer(
        self.loader, self.model, tx, cell.num_classes,
        chunk_size=int(traffic['chunk_size']),
        max_ahead=int(traffic['max_ahead']))
    self.steps_per_call = cell.steps_per_call
    self.first = self._replayed = self._slab0 = None

  def first_call(self):
    """``scan``'s first call, keeping what the stager hands the trainer
    for chunk 0."""
    stager = self.trainer._stager
    take = stager.take

    def keep(c):
      out = take(c)
      if c == 0:
        self._slab0 = out
      return out

    stager.take = keep
    try:
      return super().first_call()
    finally:
      del stager.take

  def window(self, seconds):
    """``run_window``'s counts and the program's own ``storage.*``
    counters over the window (published once a call, so the difference is
    the window's), per call where a reader wants that."""
    before = self.cell.tier_counters()
    win = run_window(self._call, seconds, 'perfbench.run_epoch',
                     self.cell.batch)
    after = self.cell.tier_counters()
    win['tier'] = {k: after[k] - before[k] for k in after}
    return win

  # ---------------------------------------------------------- the replay

  def replay(self, n, with_rows):
    """The first ``n`` batches of :meth:`first_call` as host dicts (node,
    edge_index, edge_mask, y, num_sampled_nodes, overflow; the gathered
    rows ``x`` for the first ``with_rows`` only), sampled again by the
    trainer's own sampler program with the first call's keys; ``x``
    through the program's ``tiered_gather`` from the hot prefix and the
    slab the trainer staged for chunk 0."""
    import jax
    import jax.numpy as jnp
    import graphlearn_tpu as glt
    from graphlearn_tpu import ops
    from graphlearn_tpu.storage import tiered_gather
    tr, first = self.trainer, self.first
    perm_key = jax.random.fold_in(tr._perm_key, first['epoch'])
    # the epoch's seed matrix by the all-HBM trainer's own seed program
    # (the tiered plan program computes the same and then replays)
    seed_mat, mask_mat = glt.loader.ScanTrainer._build_seed_fn(tr)(
        tr._seeds_dev, perm_key, tr._epoch_steps())
    sample_fn, label_cap = tr._sample_fn, tr._label_cap

    @jax.jit
    def one(fargs, labels, seeds, smask, base_key, count):
      res = sample_fn(*fargs, seeds, smask,
                      jax.random.fold_in(base_key, count))
      col = ops.collate_batch(res['node'], res['num_nodes'], res['row'],
                              res['col'], None, None, labels, None, None,
                              label_cap=label_cap)
      return dict(node=res['node'], edge_index=col['edge_index'],
                  edge_mask=res['edge_mask'], y=col['y'],
                  num_sampled_nodes=jnp.stack(
                      [jnp.asarray(c) for c in res['num_sampled_nodes']]),
                  overflow=res['overflow'])

    gather = jax.jit(tiered_gather)
    slab_ids, slab = (jax.device_put(a) for a in self._slab0)
    fargs = tr._sampler._fused_args()
    out = []
    for g in range(n):
      b = one(fargs, tr._labels, seed_mat[g], mask_mat[g], tr._sampler._key,
              jnp.int32(first['count0'] + g))
      if g < with_rows:
        b['x'] = gather(tr._feats, slab_ids, slab, tr._id2i, b['node'])
      out.append(jax.device_get(b))
    out[0]['slab_ids'] = np.asarray(self._slab0[0])
    self._replayed = out
    return out

  def free(self):
    """Stop the staging worker and drop the program's state so the
    reference has the chip."""
    self.trainer.close()
    self._slab0 = None
    super().free()
