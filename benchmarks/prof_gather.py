"""Profile the Pallas HBM row-gather kernel vs XLA's take on the TPU.

Run from the repo root: `python benchmarks/prof_gather.py`. The wall
clocks this script prints are per-call loop walls; kernel time comes
from jax.profiler device traces (utils.device_program_ms). Device-trace
numbers measured in rounds 2-3 on an earlier runtime, not re-measured on
this code (1M x 128 f32 table, 131k random ids):

  xla_take:    1.20 ms/call device time  (~52 GB/s useful)   <- WINNER
  pallas_128:  1.41 ms/call
  pallas_256:  1.41 ms/call
  pallas_64:   1.62 ms/call
  pallas_32:   2.40 ms/call
  pallas_512:  Mosaic compile failure (semaphore budget)

XLA's gather is already DMA-pipelined on TPU; the per-row-DMA kernel does
not beat it, so UnifiedTensor does NOT auto-route through it
(use_pallas opt-in). Kept for rigs where the balance differs and as the
framework's Pallas reference kernel.
"""
import sys
import time

sys.path.insert(0, __file__.rsplit('/', 2)[0])

import numpy as np
import jax
import jax.numpy as jnp

from graphlearn_tpu.ops.gather_pallas import gather_rows_hbm

N, F, B = 1_000_000, 128, 131072


def main():
  # NO device->host fetch inside the timed loops (it would sync every
  # call). Correctness checks run AFTER all timing.
  rng = np.random.default_rng(0)
  table = jnp.asarray(rng.random((N, F), np.float32))
  ids_np = rng.integers(0, N, B).astype(np.int32)
  ids = jnp.asarray(ids_np)
  take = jax.jit(lambda t, i: jnp.take(t, i, axis=0))

  cases = [('xla_take', lambda: take(table, ids))]
  for g in (64, 128, 256):
    cases.append((f'pallas_{g}',
                  lambda g=g: gather_rows_hbm(table, ids, block_rows=g,
                                              force=True)))
  results = []
  for name, fn in cases:
    try:
      jax.block_until_ready(fn())
      t0 = time.perf_counter()
      outs = [fn() for _ in range(50)]
      jax.block_until_ready(outs)
      dt = time.perf_counter() - t0
      gb = 50 * B * F * 4 / dt / (1024 ** 3)
      results.append(f'{name}: {dt * 20:.3f} ms/call, {gb:.1f} GB/s')
    except Exception as e:  # noqa: BLE001 — report and continue profiling
      results.append(f'{name}: FAILED {type(e).__name__}: {str(e)[:200]}')

  small = gather_rows_hbm(table, ids[:256], block_rows=64, force=True)
  np.testing.assert_allclose(np.asarray(small),
                             np.asarray(table)[ids_np[:256]])
  print('backend:', jax.default_backend())
  print('correctness OK')
  for line in results:
    print(line)


if __name__ == '__main__':
  main()
