"""Executor ``scan``: the epoch as a program. The window is whole
``ScanTrainer.run_epoch(state, max_steps=steps_per_call)`` calls back to
back, each ended by ``block_until_ready`` on its losses, until the clock
passes ``--seconds`` (``chip_smoke.py``'s scan phase, at epoch length).

Set-up builds ONE trainer with its state and drives it through its first
call — the window's own call, which also warms every shape the window
uses — with an ``ack_hook`` that copies the train state at the first chunk
boundary to the host. That copy and the call's per-step losses are what
``correct`` compares with the plain reference; the same trainer and the
state the call returned go on into the window (the hook taken off).

A scanned chunk hands out no batch, so the reference's inputs are REPLAYED
after the window: the trainer's own seed-matrix program and its sampler
program, called once more with the keys the first chunk used
(``fold_in(base_key, count0 + step)``). The replay reaches into the
trainer's private attributes — there is no public way to ask a scanned
epoch which subgraphs it trained on (PERF.md, Open questions). What ties
the replay to the timed path is the comparison itself: a chunk that had
trained on any other subgraph could not reproduce the reference's losses.
The replayed chunk also gives the valid-row counts the per-layer metrics
need (``valid_counts``): the window's own batches, not another pass's.
"""
import numpy as np

from perfbench.executors import run_window


class Executor:

  def __init__(self, cell, traffic, seed, model_dtype=None):
    import graphlearn_tpu as glt
    self.cell, self.traffic = cell, traffic
    self.loader = cell.make_loader(seed)
    self.model = cell.make_model(model_dtype)
    self.state, tx, self.params0 = cell.make_state(self.model, seed)
    self.trainer = glt.ScanTrainer(self.loader, self.model, tx,
                                   cell.num_classes,
                                   chunk_size=int(traffic['chunk_size']))
    self.steps_per_call = cell.steps_per_call
    self.first = self._replayed = None

  # ------------------------------------------------------------ the call

  def _call(self):
    import jax
    self.state, losses, _ = self.trainer.run_epoch(
        self.state, max_steps=self.steps_per_call)
    jax.block_until_ready(losses)
    return losses, self.loader.check_overflow()

  def first_call(self):
    """The window's own call, from the seed's initial state: warms the
    seed, chunk and concat programs and records what ``correct`` reads."""
    import jax
    tr = self.trainer
    start = dict(epoch=tr._epochs, count0=tr._sampler._call_count + 1)
    kept = {}

    def keep_boundary(chunk_index, start_step, k):
      if chunk_index == 0:
        kept['state'] = jax.device_get(tr._chunk_carry['state'])
        kept['steps'] = start_step + k

    tr.ack_hook = keep_boundary
    try:
      losses, overflow = self._call()
    finally:
      tr.ack_hook = None
    self.first = dict(start, losses=np.asarray(losses), overflow=overflow,
                      state=kept['state'], steps=kept['steps'])
    return self.first

  def window(self, seconds):
    return run_window(self._call, seconds, 'perfbench.run_epoch',
                      self.cell.batch)

  def traced_slice(self):
    """A short slice of the window inside an open profiler session:
    ``trace_calls`` calls of ``trace_steps`` steps through the same
    chunk program the window drives. Returns the steps it ran."""
    import jax
    steps = int(self.traffic['trace_steps'])
    n = int(self.traffic['trace_calls'])
    for _ in range(n):
      with jax.profiler.TraceAnnotation('perfbench.run_epoch'):
        self.state, losses, _ = self.trainer.run_epoch(self.state,
                                                       max_steps=steps)
      with jax.profiler.TraceAnnotation('perfbench.host_fetch'):
        jax.block_until_ready(losses)
    return steps * n

  # ---------------------------------------------------------- the replay

  def replay(self, n, with_rows):
    """The first ``n`` batches of :meth:`first_call` as host dicts
    (node, edge_index, edge_mask, y, num_sampled_nodes, overflow; the
    gathered feature rows ``x`` for the first ``with_rows`` only — they
    are 64 MB apiece), sampled again by the trainer's own programs with
    the first call's keys."""
    import jax
    import jax.numpy as jnp
    from graphlearn_tpu import ops
    tr, first = self.trainer, self.first
    perm_key = jax.random.fold_in(tr._perm_key, first['epoch'])
    seed_mat, mask_mat = tr._seed_fn(tr._seeds_dev, perm_key,
                                     tr._epoch_steps())
    sample_fn, label_cap = tr._sample_fn, tr._label_cap

    @jax.jit
    def one(fargs, feats, id2i, labels, seeds, smask, base_key, count):
      res = sample_fn(*fargs, seeds, smask,
                      jax.random.fold_in(base_key, count))
      col = ops.collate_batch(res['node'], res['num_nodes'], res['row'],
                              res['col'], feats, id2i, labels, None, None,
                              label_cap=label_cap)
      return dict(node=res['node'], edge_index=col['edge_index'],
                  edge_mask=res['edge_mask'], x=col['x'], y=col['y'],
                  num_sampled_nodes=jnp.stack(
                      [jnp.asarray(c) for c in res['num_sampled_nodes']]),
                  overflow=res['overflow'])

    fargs = tr._sampler._fused_args()
    out = []
    for g in range(n):
      b = one(fargs, tr._feats, tr._id2i, tr._labels, seed_mat[g],
              mask_mat[g], tr._sampler._key,
              jnp.int32(first['count0'] + g))
      if g >= with_rows:
        del b['x']
      out.append(jax.device_get(b))
    self._replayed = out
    return out

  def valid_counts(self):
    """The cell's counts over the batches :meth:`replay` sampled again:
    the first chunk of the window's first call."""
    return self.cell.valid_counts(self._replayed)

  def free(self):
    """Drop the program's state so the reference has the chip."""
    self.state = self.trainer = self.loader = self._replayed = None
