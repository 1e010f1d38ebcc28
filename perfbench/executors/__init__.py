"""Executors: ways to drive an epoch. What they share is the window."""
import time

import numpy as np


def run_window(call, seconds, label, batch_size):
  """Back-to-back ``call() -> (losses, overflow)`` until ``seconds`` have
  passed; every call ends in ``block_until_ready``, so the wall is device
  time plus whatever the host left idle. A call whose caps overflowed,
  whose losses are not all finite, or in whose window anything compiled
  counts its steps as failed."""
  import jax
  from graphlearn_tpu.metrics import programs
  from graphlearn_tpu.utils import count_dispatches
  compiled = programs.compile_count()
  calls = []
  with count_dispatches() as counter:
    t0 = time.perf_counter()
    while True:
      with jax.profiler.TraceAnnotation(label):
        calls.append(call())
      wall = time.perf_counter() - t0
      if wall >= seconds:
        break
  compiles = programs.compile_count() - compiled
  losses = [np.asarray(jax.device_get(l)).reshape(-1) for l, _ in calls]
  steps = sum(l.size for l in losses)
  failed = sum(l.size for l, (_, overflow) in zip(losses, calls)
               if overflow or compiles or not np.isfinite(l).all())
  return dict(steps=steps, seeds=steps * batch_size, wall_s=wall,
              failed_steps=failed, dispatches=counter.total,
              compiles=compiles, calls=len(calls),
              last_loss=float(losses[-1][-1]))
