"""Executor ``link_scan``: an edge-seeded epoch as a program. As ``scan``,
the window is whole ``ScanTrainer.run_epoch(state, max_steps=
steps_per_call)`` calls back to back, each ended by ``block_until_ready``
on its losses — over a ``LinkNeighborLoader``, so a step's seeds are
``cell.batch`` seed EDGES (what ``seeds_per_s`` counts), its negatives are
drawn in the chunk, and the trainer is asked for no ``num_classes``.

Set-up builds ONE trainer and drives it through its first call with an
``ack_hook`` that copies the train state at the first chunk boundary to
the host; that copy and the call's losses are what ``correct`` compares.

The replay asks the trainer which seed edges a step had
(``ScanTrainer.link_positions`` under the first call's order key) and runs
the sampler's link body once more under the first chunk's keys
(``fold_in(base_key, count0 + step)``): node buffer, subgraph, the seed
list, ``edge_label_index`` and labels as host arrays. As in ``scan``, what
ties the replay to the timed path is the comparison itself. The window
also carries the program's ``link.*`` counters over it (``win['link']``).
"""
from perfbench.executors import scan


class Executor(scan.Executor):
  """``scan.Executor`` as it stands — one trainer, the first call with its
  boundary copy, the window, the traced slice — over the cell's link
  loader (``cell.num_classes`` is None: the trainer asks a link job for
  none). What differs is what a link epoch can be asked: the window also
  takes the ``link.*`` counters over it, and the replay goes through the
  sampler's link body."""

  def window(self, seconds):
    from graphlearn_tpu.utils import trace
    before = trace.counters('link.')
    win = super().window(seconds)
    win['link'] = {k: v - before.get(k, 0)
                   for k, v in trace.counters('link.').items()}
    return win

  # ---------------------------------------------------------- the replay

  def replay(self, n, with_rows):
    """The first ``n`` batches of :meth:`first_call` as host dicts (pos,
    seeds, node, edge_index, edge_mask, edge_label_index, edge_label,
    num_sampled_nodes, overflow, an empty ``y``; the gathered feature rows
    ``x`` for the first ``with_rows`` only), sampled again by the
    sampler's link body with the first call's keys."""
    import jax
    import jax.numpy as jnp
    from graphlearn_tpu import ops
    tr, first = self.trainer, self.first
    order_key = jax.random.fold_in(tr._perm_key, first['epoch'])
    pos = jax.jit(tr.link_positions, static_argnums=2)(
        order_key, jnp.int32(0), n)
    link_body = tr._link_body

    @jax.jit
    def one(gargs, feats, id2i, pairs, pos, base_key, count):
      res = link_body(gargs, pairs[0][pos], pairs[1][pos],
                      jax.random.fold_in(base_key, count))
      col = ops.collate_batch(res['node'], res['num_nodes'], res['row'],
                              res['col'], feats, id2i, None, None, None)
      return dict(pos=pos, seeds=res['seeds'], node=res['node'],
                  edge_index=col['edge_index'], edge_mask=res['edge_mask'],
                  x=col['x'], y=jnp.zeros((0,), jnp.int32),
                  edge_label_index=res['link']['edge_label_index'],
                  edge_label=res['link']['edge_label'],
                  num_sampled_nodes=jnp.stack(
                      [jnp.asarray(c) for c in res['num_sampled_nodes']]),
                  link_counts=res['link_counts'],
                  overflow=res['overflow'])

    gargs = tr._sample_args()
    out = []
    for g in range(n):
      b = one(gargs, tr._feats, tr._id2i, tr._labels, pos[g],
              tr._sampler._key, jnp.int32(first['count0'] + g))
      if g >= with_rows:
        del b['x']
      out.append(jax.device_get(b))
    self._replayed = out
    return out
