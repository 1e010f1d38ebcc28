"""Executor ``typed_scan``: the ``scan`` executor's contract over a TYPED
``ScanTrainer`` — the window is whole ``ScanTrainer.run_epoch(state,
max_steps=steps_per_call)`` calls back to back over a typed
``NeighborLoader`` (seeds of one node type), each ended by
``block_until_ready`` on its losses.

Set-up, first call, window and traced slice are ``scan``'s own, inherited:
ONE trainer; the first call is the window's own call with an ``ack_hook``
that copies the train state at the first chunk boundary; the same trainer
goes on into the window. What differs is the replay, because a typed batch
is per-type dicts and a typed step draws one key per (hop, edge type): the
first chunk's batches are sampled again by the trainer's own seed-matrix
program and its typed sample program under the first call's keys, the rows
of the validated ones gathered by the chunk's own ``ops.collate_typed_batch`` —
step ``g``'s touches are ``fold_in(base_key, count0 + g * stride + j)``,
``stride`` the trainer's ``_key_stride``. Like ``scan`` it reaches into the
trainer's private attributes (PERF.md, Open questions: M7); what ties the
replay to the timed path is the comparison itself.
"""
import numpy as np

# the typed chunk's own collate: a program that cannot scan a typed graph
# has none, and a run of this executor on it ends here, before any data
from graphlearn_tpu.ops import collate_typed_batch

from perfbench.executors import scan


class Executor(scan.Executor):

  def free(self):
    """Drop the program's state AND delete its device arrays. The chunk
    program's jit keeps the trainer alive after the last reference here
    is gone, and with it every table it gathers from: 6.7 GB that the
    plain reference's step needs (PERF.md section 6, PR 30)."""
    import jax
    tr = self.trainer
    held = jax.tree.leaves((self.state, tr._feats, tr._id2i, tr._labels,
                            tr._sample_args()))
    super().free()
    self.cell.release(held)

  def replay(self, n, with_rows):
    """The first ``n`` batches of :meth:`first_call` as host dicts
    (``node`` / ``num_sampled_nodes`` per node type, ``edge_index`` /
    ``edge_mask`` per message edge type, the seed type's ``y``,
    ``overflow``; the gathered rows ``x`` per node type for the first
    ``with_rows`` only — a batch's rows are gigabytes)."""
    import jax
    import jax.numpy as jnp
    tr, first = self.trainer, self.first
    perm_key = jax.random.fold_in(tr._perm_key, first['epoch'])
    seed_mat, mask_mat = tr._seed_fn(tr._seeds_dev, perm_key,
                                     tr._epoch_steps())
    sample_fn, step_keys, stride = (tr._sample_fn, tr._step_keys,
                                    tr._key_stride)
    t_in, label_cap = self.cell.input_type, tr._label_cap

    @jax.jit
    def sample(fargs, seeds, smask, base_key, count):
      res = sample_fn(fargs, seeds, smask, step_keys(base_key, count))
      return dict(node=res['node'], edge_mask=res['edge_mask'],
                  edge_index={et: jnp.stack([r, res['col'][et]])
                              for et, r in res['row'].items()},
                  num_sampled_nodes={
                      t: jnp.stack([jnp.asarray(c) for c in v])
                      for t, v in res['num_sampled_nodes'].items()},
                  overflow=res['overflow'])

    @jax.jit
    def rows(feats, id2i, labels, node):
      x, _, y = collate_typed_batch(node, {}, {}, feats, id2i, labels, t_in,
                                    label_cap=label_cap)
      return x, y

    fargs = tr._sample_args()
    out = []
    for g in range(n):
      b = sample(fargs, seed_mat[g], mask_mat[g], tr._sampler._key,
                 jnp.int32(first['count0'] + g * stride))
      if g < with_rows:
        x, y = rows(tr._feats, tr._id2i, tr._labels, b['node'])
        b = dict(b, x=x, y=y)
      b = jax.device_get(b)
      if 'y' not in b:
        # the seed rows' labels by the generator's own array, so every
        # replayed batch has a ``y``
        b['y'] = self.cell.label[np.maximum(
            b['node'][t_in][:self.cell.batch], 0)]
      out.append(b)
    self._replayed = out
    return out
