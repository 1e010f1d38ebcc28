"""Executor ``mesh_scan``: the epoch as ONE program across the chips. The
window is whole ``DistScanTrainer.run_epoch(state,
max_steps=steps_per_call)`` calls back to back over a collocated
``DistNeighborLoader`` (P partitions, one a chip, ``batch`` seeds each),
each ended by ``block_until_ready`` on its losses.

The contract is ``scan``'s (``executors/scan.py``): ONE trainer; the first
call is the window's own call, with an ``ack_hook`` that copies the
(replicated) train state at the first chunk boundary; the same trainer
goes on into the window. A step trains ``P * batch`` seeds.

A scanned chunk hands out no batch, so the first chunk's P x K shard
batches are REPLAYED after the window under the same keys: the trainer's
own seed-matrix program, then the per-step program of the SAME sampler
(``DistNeighborSampler.sample_from_nodes`` with ``_keys_for(count0 + g)`` —
the stream the chunk folds in, as the mesh tests hold bit for bit), and
for the validated steps the loader's own collate (``DistFeature.get``: the
cached miss-only lookup the chunk inlines). Like ``scan`` it reaches into
the trainer's private attributes (PERF.md, Open questions: M7); what ties
the replay to the timed path is the comparison itself.
"""
import numpy as np

from perfbench.executors import run_window, scan


class Executor(scan.Executor):
  """``scan``'s first call, traced slice and counts, inherited, over a
  ``DistScanTrainer``; the window counts P x batch seeds a step and the
  program's counters, and the replay goes through the mesh sampler."""

  def __init__(self, cell, traffic, seed, model_dtype=None):
    import graphlearn_tpu as glt
    self.cell, self.traffic = cell, traffic
    self.loader = cell.make_loader(seed)
    self.model = cell.make_model(model_dtype)
    self.state, tx, self.params0 = cell.make_state(self.model, seed)
    self.trainer = glt.loader.DistScanTrainer(
        self.loader, self.model, tx, cell.num_classes,
        chunk_size=int(traffic['chunk_size']))
    self.steps_per_call = cell.steps_per_call
    self.first = self._replayed = None

  def window(self, seconds):
    """``run_window``'s counts, a step being P x batch seeds, and the
    program's own feature counters over the window (they are published
    once an epoch, so the difference is the window's)."""
    before = self.cell.feature_counters()
    win = run_window(self._call, seconds, 'perfbench.run_epoch',
                     self.cell.batch * self.cell.parts)
    after = self.cell.feature_counters()
    win['counters'] = {k: after[k] - before[k] for k in after}
    return win

  # ---------------------------------------------------------- the replay

  def replay(self, n, with_rows):
    """The first ``n`` steps of :meth:`first_call` as host dicts, every
    array with its leading ``[P, ...]`` shard axis (node, edge_index,
    edge_mask, num_sampled_nodes, overflow; the gathered rows ``x`` and
    the seed labels ``y`` for the first ``with_rows`` steps only)."""
    import jax
    import jax.numpy as jnp
    tr, first = self.trainer, self.first
    sampler = tr._sampler
    perm_key = jax.random.fold_in(tr._perm_key, first['epoch'])
    seed_mat, mask_mat = tr._seed_fn(tr._seeds_dev, perm_key,
                                     len(self.loader))
    seed_mat, mask_mat = np.asarray(seed_mat), np.asarray(mask_mat)
    label_cap = tr._label_cap
    out = []
    for g in range(n):
      res = sampler.sample_from_nodes(
          seed_mat[:, g], seed_mask=mask_mat[:, g],
          keys=sampler._keys_for(jnp.int32(first['count0'] + g)))
      b = dict(node=res.node, edge_mask=res.edge_mask,
               edge_index=jnp.stack([res.row, res.col], axis=1),
               num_sampled_nodes=res.num_sampled_nodes,
               overflow=res.metadata['overflow'])
      if g < with_rows:
        b['x'], b['y'] = sampler.collate(
            res, self.loader.data.node_labels, label_cap=label_cap)
      out.append(jax.device_get(b))
    self._replayed = out
    return out
