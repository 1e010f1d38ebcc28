"""Executor ``table_scan``: the ``typed_scan`` executor over a typed graph
whose non-labelled node types have learned tables — the window is whole
``ScanTrainer.run_epoch(state, max_steps=steps_per_call)`` calls back to
back, each ended by ``block_until_ready`` on its losses; the train state it
donates from call to call holds the tables and their two Adam moments.

What differs from ``typed_scan``: the window also takes the program's
``table.*`` counters over it (``win['table']``, published once a call), and
the replay runs the chunk's own typed collate WITH the table types, so a
validated batch's ``x`` holds the labelled type's rows and each table
type's node ids as the chunk emitted them, and the first batch carries
``grad_rows`` — per table type the rows whose gradient, in the program's
own loss over that batch from the initial state, is not exactly 0 (the
family's ``pad_grad_rows`` reads it). As in ``typed_scan``, what ties the
replay to the timed path is the comparison itself.
"""
import numpy as np

# a program that cannot train learned tables has no table module, and a
# run of this executor on it ends here, before any data
from graphlearn_tpu.models.tables import NODE_TABLES

from perfbench.executors import typed_scan


class Executor(typed_scan.Executor):

  def window(self, seconds):
    from graphlearn_tpu.utils import trace
    before = trace.counters('table.')
    win = super().window(seconds)
    win['table'] = {k: v - before.get(k, 0)
                    for k, v in trace.counters('table.').items()}
    return win

  def replay(self, n, with_rows):
    """The first ``n`` batches of :meth:`first_call` as host dicts (per
    node type ``node`` / ``num_sampled_nodes``, per message edge type
    ``edge_index`` / ``edge_mask``, the seeds' ``y``, ``overflow``; for the
    first ``with_rows`` the chunk's own ``x``: rows of the labelled type,
    node ids of a table type), and on the first batch ``grad_rows``."""
    import jax
    import jax.numpy as jnp
    from graphlearn_tpu import ops
    from graphlearn_tpu.models import train as train_lib
    tr, first = self.trainer, self.first
    perm_key = jax.random.fold_in(tr._perm_key, first['epoch'])
    seed_mat, mask_mat = tr._seed_fn(tr._seeds_dev, perm_key,
                                     tr._epoch_steps())
    sample_fn, step_keys, stride = (tr._sample_fn, tr._step_keys,
                                    tr._key_stride)
    t_in, label_cap, id_types = (self.cell.input_type, tr._label_cap,
                                 tr._id_types)

    @jax.jit
    def sample(fargs, feats, id2i, labels, seeds, smask, base_key, count):
      res = sample_fn(fargs, seeds, smask, step_keys(base_key, count))
      x, edge_index, y = ops.collate_typed_batch(
          res['node'], res['row'], res['col'], feats, id2i, labels, t_in,
          label_cap=label_cap, id_types=id_types)
      return dict(node=res['node'], edge_mask=res['edge_mask'],
                  edge_index=edge_index, x=x, y=y,
                  num_seed_nodes=res['num_sampled_nodes'][t_in][0],
                  num_sampled_nodes={
                      t: jnp.stack([jnp.asarray(c) for c in v])
                      for t, v in res['num_sampled_nodes'].items()},
                  overflow=res['overflow'])

    loss_fn = train_lib.make_loss_fn(self.model, self.cell.num_classes)

    @jax.jit
    def first_grad_rows(params, batch):
      g = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
      return {t: (v != 0).any(axis=1)
              for t, v in g['params'][NODE_TABLES].items()}

    fargs = tr._sample_args()
    out = []
    for g in range(n):
      b = sample(fargs, tr._feats, tr._id2i, tr._labels, seed_mat[g],
                 mask_mat[g], tr._sampler._key,
                 jnp.int32(first['count0'] + g * stride))
      if g == 0:
        # the program's own first gradient, from the initial state, over
        # the batch the chunk trained on first
        step = {k: b[k] for k in ('x', 'edge_index', 'edge_mask', 'y',
                                  'num_seed_nodes')}
        moved = jax.device_get(first_grad_rows(
            jax.tree.map(jnp.asarray, self.params0), step))
      if g >= with_rows:
        del b['x']
      del b['num_seed_nodes']
      b = jax.device_get(b)
      if g == 0:
        b['grad_rows'] = {t: np.flatnonzero(v) for t, v in moved.items()}
      out.append(b)
    self._replayed = out
    return out
