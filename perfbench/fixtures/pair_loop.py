"""Test-only executor ``pair_loop``: the ``two_table`` family's per-batch
loop — the cell's typed batches through the cell's jitted step, a call
ended by ``block_until_ready``. It answers what ``run.py`` and
``control.py`` ask of any executor (perfbench/README.md) and nothing else.
The batches are in hand, so ``replay`` hands back the first call's own."""
import numpy as np

from perfbench.executors import run_window


class Executor:

  def __init__(self, cell, traffic, seed):
    self.cell, self.traffic = cell, traffic
    self.loader = cell.make_loader(seed)
    self.state, tx, self.params0 = cell.make_state(seed)
    self.step = cell.make_step(tx)
    self._kept, self._slice = [], []

  def _call(self, steps, seen=None, keep_state_at=0):
    import jax
    losses, kept_state = [], None
    for i, b in zip(range(steps), self.loader):
      if seen is not None:
        seen.append(b)
      self.state, loss = self.step(self.state, b)
      losses.append(loss)
      if i + 1 == keep_state_at:
        kept_state = self.state
    jax.block_until_ready(losses[-1])
    return jax.numpy.stack(losses), False, kept_state

  def first_call(self):
    import jax
    n = int(self.traffic['reference_steps'])
    losses, overflow, kept = self._call(self.cell.steps_per_call,
                                        self._kept, n)
    return dict(losses=np.asarray(losses), overflow=overflow, steps=n,
                state=jax.device_get(kept))

  def window(self, seconds):
    return run_window(lambda: self._call(self.cell.steps_per_call)[:2],
                      seconds, 'perfbench.pair_loop', self.cell.batch)

  def traced_slice(self):
    import jax
    n = int(self.traffic['slice_steps'])
    with jax.profiler.TraceAnnotation('perfbench.pair_loop'):
      self._call(n, self._slice)
    return n

  def valid_counts(self):
    import jax
    return self.cell.valid_counts(jax.device_get(self._slice))

  def replay(self, n, with_rows):
    import jax
    rows = ('x_user', 'x_item')
    return [jax.device_get(b if g < with_rows else
                           {k: v for k, v in b.items() if k not in rows})
            for g, b in enumerate(self._kept[:n])]

  def free(self):
    self.state = self.loader = self.step = None
    self._kept = self._slice = []
