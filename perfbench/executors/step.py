"""Executor ``step``: the per-batch loop — ``NeighborLoader`` iteration +
``make_train_step``, three program launches a batch and nothing fetched
until a call ends (``chip_smoke.py``'s per_step and trace phases).

A call is ``steps_per_call`` batches of one pass over the loader, ended by
``block_until_ready`` on the last loss. It is the executor of the
``step-exact`` mix, and the scanned cells' traced runs use it for their
slice (b): the only place where sampling, collate and the model are
separate device programs today, so ``sample_ms``, ``collate_ms``,
``train_ms`` and the valid-row counts are read here.

Here the batches are in hand, so nothing is replayed: the first
``reference_steps`` batches of the first call are kept for the reference,
with the state after them.
"""
import numpy as np

from perfbench.executors import run_window


class Executor:

  def __init__(self, cell, traffic, seed, model_dtype=None):
    from graphlearn_tpu.models import train as train_lib
    self.cell, self.traffic = cell, traffic
    self.loader = cell.make_loader(seed)
    self.model = cell.make_model(model_dtype)
    self.state, tx, self.params0 = cell.make_state(self.model, seed)
    self.train_step, _ = train_lib.make_train_step(self.model, tx,
                                                   cell.num_classes)
    self.steps_per_call = cell.steps_per_call
    self.first = None
    self._kept = []

  def _call(self, steps, keep=0):
    import jax
    from graphlearn_tpu.models import train as train_lib
    losses, kept_state = [], None
    for i, b in zip(range(steps), self.loader):
      if i < keep:
        self._kept.append(b)
      self.state, loss, _ = self.train_step(self.state,
                                            train_lib.batch_to_dict(b))
      losses.append(loss)
      if i + 1 == keep:
        kept_state = self.state
    jax.block_until_ready(losses[-1])
    return losses, self.loader.check_overflow(), kept_state

  def first_call(self):
    import jax
    n = int(self.traffic.get('reference_steps', 3))
    losses, overflow, kept_state = self._call(self.steps_per_call, keep=n)
    self.first = dict(losses=np.asarray(jax.device_get(losses)),
                      overflow=overflow, steps=n,
                      state=jax.device_get(kept_state))
    return self.first

  def window(self, seconds):
    return run_window(lambda: self._call(self.steps_per_call)[:2], seconds,
                      'perfbench.loader_pass', self.cell.batch)

  def traced_slice(self):
    """``step_slice_steps`` batches inside an open profiler session;
    keeps their masks for :meth:`valid_counts`."""
    import jax
    from graphlearn_tpu.models import train as train_lib
    n = int(self.traffic['step_slice_steps'])
    self._slice = []
    loss = None
    for _, b in zip(range(n), self.loader):
      with jax.profiler.TraceAnnotation('perfbench.step'):
        self.state, loss, _ = self.train_step(self.state,
                                              train_lib.batch_to_dict(b))
      self._slice.append((b.num_sampled_nodes, b.edge_mask))
    with jax.profiler.TraceAnnotation('perfbench.host_fetch'):
      jax.block_until_ready(loss)
    return n

  def valid_counts(self):
    """Mean valid node rows per hop and valid edges per hop over the
    traced slice's batches, and the node buffer's rows."""
    import jax
    eo = (0,) + tuple(self.cell.edge_offsets)
    nodes, edges = [], []
    for nsn, em in self._slice:
      nodes.append([int(c) for c in jax.device_get(list(nsn))])
      em = np.asarray(em)
      edges.append([int(em[eo[h]:eo[h + 1]].sum())
                    for h in range(len(eo) - 1)])
    return dict(nodes=np.mean(nodes, 0).tolist(),
                edges=np.mean(edges, 0).tolist(),
                buffer_rows=int(self.cell.node_offsets[-1]))

  def replay(self, n, with_rows):
    import jax
    out = []
    for g, b in enumerate(self._kept[:n]):
      d = dict(node=b.node, edge_index=b.edge_index, edge_mask=b.edge_mask,
               y=b.y, num_sampled_nodes=np.asarray(
                   jax.device_get(list(b.num_sampled_nodes))),
               overflow=b.metadata.get('overflow', False))
      if g < with_rows:
        d['x'] = b.x
      out.append(jax.device_get(d))
    return out

  def free(self):
    self.state = self.loader = self.train_step = None
    self._kept = []
