"""Executor ``step``: the per-batch loop — ``NeighborLoader`` iteration +
``make_train_step``, three program launches a batch and nothing fetched
until a call ends (``chip_smoke.py``'s per_step and trace phases).

A call is ``steps_per_call`` batches of one pass over the loader, ended by
``block_until_ready`` on the last loss. It is the executor of the
``step-exact`` mix, which no cell uses yet (PERF.md section 7 row 1):
the only place where sampling, collate and the model are separate device
programs (``jit_sample_*``, ``jit_collate_batch``, ``jit_train_step``), so
the cell that takes it can bring per-program readers over its traced slice
(sums over the ``XLA Modules`` lane, in files of their own).

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
      self._slice.append(dict(num_sampled_nodes=list(b.num_sampled_nodes),
                              edge_mask=b.edge_mask))
    with jax.profiler.TraceAnnotation('perfbench.host_fetch'):
      jax.block_until_ready(loss)
    return n

  def valid_counts(self):
    """The cell's counts over the traced slice's batches."""
    import jax
    return self.cell.valid_counts(jax.device_get(self._slice))

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
