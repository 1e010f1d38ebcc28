"""Autotune probe for the r13 kernel campaign: gather v2 (run-segmented
multi-row DMA, ops.gather_rows_hbm2) and the fused sample+gather hop
(ops.sample_hop_fused) vs their XLA paths, across the
``block_rows x run_span`` / ``window x block_seeds`` grids and several
id DISTRIBUTIONS (the v2 kernel's win condition is locality, so the
distribution axis is as load-bearing as the tile axes).

Run on TPU from the repo root: ``python benchmarks/prof_gather2.py``
(add ``--quick`` for a 2x2 grid smoke). Kernel time is the per-config
`jax.profiler` device trace each cell captures under
/tmp/glt_prof_gather2_*. The table printer
reads those traces (utils.device_program_ms), so the numbers shown ARE
device ms when the TPU lane is present, dispatch-wall otherwise
(labelled).

Interpretation guide (what decides the routing flags):
  - gather v2 wins a cell when its device ms beats XLA take's on the
    SAME ids; the shipping default flips UnifiedTensor.use_pallas_v2
    only for a win on the 'sorted'/'runs' distributions (its target
    workload — staging slab gathers); a 'random' loss is expected (the
    sort + unsort adds work, PERF.md) and acceptable if trace-attributed.
  - fused hop wins when one staged-segment DMA per seed beats k element
    gathers; hub-heavy frontiers dilute the win (deg > window seeds pay
    k row DMAs) — the 'zipf' seed mix measures that dilution.
"""
import argparse
import shutil
import sys
import time

sys.path.insert(0, __file__.rsplit('/', 2)[0])

import numpy as np


def _dists(rng, n, b):
  """The id-distribution axis: each is a [b] int32 vector."""
  contig0 = rng.integers(0, n - b)
  return {
      # uniform random: v2's worst case (every slot its own DMA + sort)
      'random': rng.integers(0, n, b).astype(np.int32),
      # sorted unique: the staging/slab shape (presorted=True path)
      'sorted': np.sort(rng.choice(n, b, replace=False)).astype(np.int32),
      # duplicate-heavy: hot rows repeated (cache-miss fan-in shape)
      'dup': rng.choice(rng.integers(0, n, b // 16), b).astype(np.int32),
      # one contiguous span: the upper bound for run coverage
      'runs': np.arange(contig0, contig0 + b, dtype=np.int32),
  }


def _timed(jax, fn, trace_dir, prefix, iters):
  from graphlearn_tpu.utils import device_program_ms
  jax.block_until_ready(fn())
  shutil.rmtree(trace_dir, ignore_errors=True)
  jax.profiler.start_trace(trace_dir)
  t0 = time.perf_counter()
  outs = [fn() for _ in range(iters)]
  jax.block_until_ready(outs)
  wall_ms = (time.perf_counter() - t0) / iters * 1e3
  jax.profiler.stop_trace()
  for name, (ms, _) in device_program_ms(trace_dir).items():
    if name.startswith(prefix):
      return ms, 'device'
  return wall_ms, 'wall'


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--num-rows', type=int, default=1_000_000)
  ap.add_argument('--feat', type=int, default=128)
  ap.add_argument('--ids', type=int, default=131072)
  ap.add_argument('--iters', type=int, default=20)
  ap.add_argument('--quick', action='store_true')
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  from graphlearn_tpu import ops
  from graphlearn_tpu.ops.gather_pallas import _gather_rows_hbm2_impl

  n, f, b = args.num_rows, args.feat, args.ids
  rng = np.random.default_rng(0)
  table = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
  dists = _dists(rng, n, b)
  on_tpu = jax.default_backend() == 'tpu'
  interp = not on_tpu   # CPU smoke runs the interpreter on tiny shapes
  if interp and not args.quick:
    print('backend is not TPU: forcing --quick (interpret-mode smoke)')
    args.quick = True
  if args.quick and interp:
    # interpret-mode DMA emulation pays per UNROLLED slot at trace time:
    # keep the smoke shapes tiny or the probe spends minutes compiling
    n, b = 2048, 128
    table = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    dists = _dists(rng, n, b)

  take = jax.jit(lambda t, i: jnp.take(t, i, axis=0))
  if args.quick:
    grid_blocks, grid_spans = (16, 64), (4, 8)
  else:
    grid_blocks, grid_spans = (64, 128, 256, 512), (1, 4, 8, 16, 32)

  print(f'backend={jax.default_backend()}  table=[{n}, {f}] f32  '
        f'ids={b}  iters={args.iters}')
  print('\n=== gather v2: device ms/call (XLA take baseline per dist) ===')
  for dname, ids_np in dists.items():
    ids = jnp.asarray(ids_np)
    base_ms, src = _timed(jax, lambda: take(table, ids),
                          f'/tmp/glt_prof_gather2_take_{dname}',
                          'jit_', args.iters)
    presorted = bool((np.diff(ids_np) >= 0).all())
    print(f'  [{dname}] xla_take: {base_ms:.3f} ms ({src}; '
          f'presorted={presorted})')
    for br in grid_blocks:
      for span in grid_spans:
        tag = f'{dname}_b{br}_s{span}'
        try:
          ms, src = _timed(
              jax,
              lambda br=br, span=span: _gather_rows_hbm2_impl(
                  table, ids, br, span, presorted, interp),
              f'/tmp/glt_prof_gather2_{tag}', 'jit_', args.iters)
          verdict = 'WIN' if ms < base_ms else 'lose'
          print(f'    v2 block_rows={br:4d} run_span={span:3d}: '
                f'{ms:8.3f} ms ({src})  {verdict} '
                f'x{base_ms / ms:.2f}')
        except Exception as e:  # noqa: BLE001 — record, keep probing
          print(f'    v2 block_rows={br:4d} run_span={span:3d}: FAILED '
                f'{type(e).__name__}: {str(e)[:120]}')

  # ---- fused hop grid --------------------------------------------------
  print('\n=== fused sample+gather hop (window x block_seeds grid) ===')
  e = n * 8 if not (args.quick and interp) else n * 4
  rows = rng.integers(0, n, e)
  cols = np.sort(rng.integers(0, n, e))  # arbitrary; rows sorted below
  order = np.argsort(rows, kind='stable')
  rows = rows[order]
  indptr = np.concatenate(
      [[0], np.cumsum(np.bincount(rows, minlength=n))]).astype(np.int32)
  ip = jnp.asarray(indptr)
  ind = jnp.asarray(cols[order].astype(np.int32))
  meta = jnp.stack([ip[:-1], ip[1:] - ip[:-1]], 1).astype(jnp.int32)
  sb = min(b, 16384) if not (args.quick and interp) else 64
  seed_mixes = {
      'uniform': rng.integers(0, n, sb).astype(np.int32),
      'zipf': (rng.zipf(1.5, sb) % n).astype(np.int32),  # hub-heavy
  }
  key = jax.random.fold_in(jax.random.PRNGKey(0), 1)
  k = 10
  mask = jnp.ones((sb,), bool)
  for mix, seeds_np in seed_mixes.items():
    seeds = jnp.asarray(seeds_np)
    base_ms, src = _timed(
        jax, lambda: ops.uniform_sample(ip, ind, seeds, mask, k, key,
                                        meta=meta),
        f'/tmp/glt_prof_fh_xla_{mix}', 'jit_uniform_sample', args.iters)
    print(f'  [{mix}] xla_hop (k={k}, {sb} seeds): {base_ms:.3f} ms '
          f'({src})')
    for window in ((128,) if args.quick else (128, 256, 512, 1024)):
      blocks = ops.build_indices128(ind, min_rows=window // 128 + 1)
      for bs in ((16,) if args.quick else (64, 128, 256)):
        try:
          ms, src = _timed(
              jax,
              lambda window=window, bs=bs, blocks=blocks:
              ops.sample_hop_fused(ip, ind, blocks, seeds, mask, k, key,
                                   meta=meta, window=window,
                                   block_seeds=bs, interpret=interp),
              f'/tmp/glt_prof_fh_{mix}_w{window}_b{bs}',
              'jit_sample_hop_fused', args.iters)
          verdict = 'WIN' if ms < base_ms else 'lose'
          print(f'    fused window={window:5d} block_seeds={bs:4d}: '
                f'{ms:8.3f} ms ({src})  {verdict} x{base_ms / ms:.2f}')
        except Exception as e:  # noqa: BLE001
          print(f'    fused window={window:5d} block_seeds={bs:4d}: '
                f'FAILED {type(e).__name__}: {str(e)[:120]}')


if __name__ == '__main__':
  main()
