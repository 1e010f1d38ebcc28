"""Pallas TPU kernel: random row gather from an HBM-resident table.

TPU-native replacement for the reference's UnifiedTensor gather kernel
(/root/reference/graphlearn_torch/csrc/cuda/unified_tensor.cu:48-81, a
warp-per-row UVA gather). The feature lookup is the biggest per-batch byte
mover in GNN training (PERF.md: ~40x the sampler's budget), and XLA lowers
`jnp.take` over a large HBM table through generic dynamic-gather machinery.

``gather_rows_hbm2`` issues multi-row DMAs over contiguous id-RUNS. The
repo's design rule (ops/induce_merge.py, PERF.md): sorts beat random
access on TPU, so it sorts the ids on device (one key+payload lax.sort),
segments the sorted ids into maximal runs of STRICTLY CONSECUTIVE table
rows (split at ``run_span`` and at grid-block boundaries), and issues
ONE async copy per full run instead of per row — contiguous source AND
destination, so a sorted or locality-heavy id vector collapses from B
transactions to ~B/run_span. Slots not covered by a full-span run keep
a single-row copy. The unsort back to caller order is one more payload
sort + a [B, F] row permutation; callers whose ids are ALREADY
sorted-unique (the tiered-storage staging planner, searchsorted slab
gathers) pass ``presorted=True`` and skip both. Not yet timed on the
chip (ROADMAP.md S6), so routing stays opt-in behind
``UnifiedTensor.use_pallas_v2`` / ``EmbeddingStore.use_pallas_v2``.

Calling this op IS asking for the kernel. Off-TPU it falls back
to the bit-identical `jnp.take` (same clamped-id contract) so CPU tests
and examples run; tests exercise the kernel via interpret=True on small
shapes. On TPU there is no fallback: a table Mosaic cannot serve (width
not 128-lane aligned) raises with the reason instead of quietly running
XLA's gather under the kernel's name.
"""
import functools
import time
import warnings

import jax
import jax.numpy as jnp


def _require_lane_aligned(owner: str, table):
  """Mosaic HBM row slices must be 128-lane aligned; on TPU a kernel that
  was asked for never quietly becomes XLA's gather."""
  if table.shape[1] % 128 != 0:
    raise ValueError(
        f'{owner}: a Pallas gather was asked for, but the table width '
        f'{table.shape[1]} is not a multiple of 128 lanes — Mosaic cannot '
        'lower the HBM row DMA. Pad the width, or gather with jnp.take '
        '(clear the use_pallas_v2 flag); there is no XLA fallback on TPU')


def _kernel_route(name: str, table, ids, interpret: bool, force: bool):
  """True -> run the Pallas kernel, False -> the off-TPU ``jnp.take``
  fallback. On TPU a request the kernel cannot serve raises."""
  if ids.shape[0] == 0:
    return False
  if interpret:
    return True        # the interpreter has no lane constraint
  if jax.default_backend() == 'tpu':
    _require_lane_aligned(name, table)
    return True
  if force and table.shape[1] % 128 != 0:
    warnings.warn(
        f'{name}(force=True): table width {table.shape[1]} is not '
        '128-lane aligned — Mosaic cannot lower the row DMA; falling '
        'back to jnp.take', stacklevel=3)
    return False
  return force


def table_kernel_ok(owner: str, table) -> bool:
  """Whether a store that was ASKED to route its gathers through these
  kernel (``use_pallas_v2``) can: False off-TPU (CPU runs keep the XLA
  gather), True on TPU — and a raise on TPU when the table cannot be
  served, so a set flag never means a silent XLA gather there."""
  if jax.default_backend() != 'tpu':
    return False
  _require_lane_aligned(owner, table)
  if len(table.sharding.device_set) != 1:
    raise ValueError(
        f'{owner}: a Pallas gather was asked for, but the table is '
        f'sharded over {len(table.sharding.device_set)} devices — the '
        'row-DMA kernels serve a single-device table. Clear the '
        'use_pallas_v2 flag for sharded stores')
  return True


# ------------------------------------------------------------------ v2

# plan encoding: bits 30-31 carry the per-slot DMA kind, low 30 bits the
# clamped table row. Tables beyond 2^30 rows must shard (same bound as
# the int32 CSR contract elsewhere in the stack). NOTE: kind 2 occupies
# the int32 SIGN bit, so decoding must mask after the shift —
# ``(plan >> 30) & 3`` — or an arithmetic right shift turns it into -2.
_KIND_SINGLE = 0   # one row DMA for this slot
_KIND_RUN = 1      # this slot starts a full ``run_span``-row DMA
_KIND_COVERED = 2  # covered by a preceding run start: no DMA
_ROW_MASK = (1 << 30) - 1


def decode_gather_plan(plan):
  """(kind, row) arrays from a packed :func:`plan_gather_runs` plan —
  the sign-bit-safe decode every consumer should use."""
  return (plan >> 30) & 3, plan & _ROW_MASK


def plan_gather_runs(sid, n_rows: int, block_rows: int, run_span: int):
  """Per-slot DMA plan over a SORTED id vector (host-free, pure XLA).

  A slot either copies its own row (kind 0), starts one contiguous
  ``run_span``-row copy covering itself and the next ``run_span - 1``
  slots (kind 1 — only when those slots hold strictly consecutive ids,
  the run does not cross a grid-block boundary, and the span stays
  inside the table), or is covered by such a start (kind 2). Only
  FULL-length runs use the multi-row copy: a shorter run's copy would
  overwrite the slots of whatever run follows it (DMA sizes are static),
  so partial runs decompose into singles. Returns the packed int32 plan;
  decode with :func:`decode_gather_plan` (kind 2 rides the sign bit, so
  a bare ``plan >> 30`` mis-decodes it as -2).
  """
  b = sid.shape[0]
  j = jnp.arange(b, dtype=jnp.int32)
  prev = jnp.concatenate([jnp.full((1,), -2, jnp.int32), sid[:-1]])
  # maximal +1-step runs, broken at grid-block boundaries (a run must
  # stay inside the output block its DMA writes)
  start0 = (sid != prev + 1) | (j % block_rows == 0)
  origin = jax.lax.cummax(jnp.where(start0, j, -1))
  # split every run_span slots from the run origin: every resulting run
  # is <= run_span long, and a FULL run is exactly run_span
  is_start = start0 | ((j - origin) % run_span == 0)
  start_pos = jax.lax.cummax(jnp.where(is_start, j, -1))
  # run length = next start (strictly after me) - my start
  nxt = jnp.flip(jax.lax.cummin(jnp.flip(
      jnp.where(is_start, j, b).astype(jnp.int32))))
  nxt_after = jnp.concatenate([nxt[1:], jnp.full((1,), b, jnp.int32)])
  run_len = nxt_after - start_pos
  full = is_start & (run_len == run_span) & (sid + run_span <= n_rows)
  # propagate the start's ``full`` verdict across its run (packed cummax
  # rides the run rank in the high bits — ops/induce_merge.py's trick)
  grp = jnp.cumsum(is_start.astype(jnp.int32))
  fullv = jax.lax.cummax(
      (grp << 1) | (full & is_start).astype(jnp.int32)) & 1
  kind = jnp.where(fullv == 1,
                   jnp.where(is_start, _KIND_RUN, _KIND_COVERED),
                   _KIND_SINGLE).astype(jnp.int32)
  return sid | (kind << 30)


def _gather2_kernel_factory(span):
  def kernel(plan_ref, table_ref, out_ref, sems):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    g = out_ref.shape[0]

    def dmas(slot):
      v = plan_ref[i * g + slot]
      rid = v & _ROW_MASK
      kind = (v >> 30) & 3   # mask: kind 2 rides the sign bit
      single = pltpu.make_async_copy(table_ref.at[rid], out_ref.at[slot],
                                     sems.at[slot])
      run = pltpu.make_async_copy(table_ref.at[pl.ds(rid, span)],
                                  out_ref.at[pl.ds(slot, span)],
                                  sems.at[slot])
      return kind, single, run

    def issue(slot, carry):
      kind, single, run = dmas(slot)

      @pl.when(kind == _KIND_SINGLE)
      def _():
        single.start()

      @pl.when(kind == _KIND_RUN)
      def _():
        run.start()
      return carry

    jax.lax.fori_loop(0, g, issue, None, unroll=True)

    def drain(slot, carry):
      kind, single, run = dmas(slot)

      @pl.when(kind == _KIND_SINGLE)
      def _():
        single.wait()

      @pl.when(kind == _KIND_RUN)
      def _():
        run.wait()
      return carry

    jax.lax.fori_loop(0, g, drain, None, unroll=True)
  return kernel


@functools.partial(jax.jit,
                   static_argnames=('block_rows', 'run_span', 'presorted',
                                    'interpret'))
def _gather_rows_hbm2_impl(table, ids, block_rows: int = 256,
                           run_span: int = 8, presorted: bool = False,
                           interpret: bool = False):
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  n, f = table.shape
  assert n <= _ROW_MASK, 'gather v2 plan packs rows into 30 bits'
  b = ids.shape[0]
  ids = jnp.clip(ids, 0, n - 1).astype(jnp.int32)
  if presorted:
    sid, inv = ids, None
  else:
    iota = jnp.arange(b, dtype=jnp.int32)
    sid, perm = jax.lax.sort((ids, iota), num_keys=1)
    _, inv = jax.lax.sort((perm, iota), num_keys=1)
  g = min(block_rows, b)
  span = min(run_span, g)
  pad = (-b) % g
  if pad:
    # pad slots hold row 0 as their own singles; sliced off below
    sid = jnp.concatenate([sid, jnp.zeros((pad,), jnp.int32)])
  plan = plan_gather_runs(sid, n, g, span)
  grid = (b + pad) // g

  out = pl.pallas_call(
      _gather2_kernel_factory(span),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1,
          grid=(grid,),
          in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
          out_specs=pl.BlockSpec((g, f), lambda i, plan_ref: (i, 0)),
          scratch_shapes=[pltpu.SemaphoreType.DMA((g,))],
      ),
      out_shape=jax.ShapeDtypeStruct((b + pad, f), table.dtype),
      interpret=interpret,
  )(plan, table)
  out = out[:b] if pad else out
  return out if presorted else jnp.take(out, inv, axis=0)


def gather_rows_hbm2(table, ids, block_rows: int = 256, run_span: int = 8,
                     presorted: bool = False, interpret: bool = False,
                     force: bool = False):
  """Gather ``table[ids]`` via run-segmented multi-row async DMAs (v2).

  Sorts the ids on device (skipped with ``presorted=True`` — the caller
  asserts ids are ascending; duplicates are fine, they break runs), then
  copies each full ``run_span``-long stretch of consecutive rows with
  ONE DMA and everything else row-by-row. Bit-identical to
  ``jnp.take(table, clip(ids), axis=0)`` on every path, including the
  off-TPU / misaligned-width fallback.

  Args:
    table: [N, F] device array (HBM-resident; F must be 128-lane aligned
      for the kernel path — on TPU a misaligned width raises).
    ids: [B] int32 row indices (clamped to [0, N)).
    block_rows: output rows per grid step.
    run_span: rows per multi-row DMA (1 degenerates to one DMA per row
      plus the sort).
    presorted: ids are already ascending — skips the sort AND the unsort
      row permutation (the tiered staging planner's slab gathers and
      any searchsorted-driven caller qualify).
    interpret: run the Pallas interpreter (CPU tests).
    force: run the kernel even off-TPU; off-TPU it still falls back
      (with a warning) on misaligned widths.

  Returns [B, F] gathered rows.
  """
  from .. import metrics
  if not _kernel_route('gather_rows_hbm2', table, ids, interpret, force):
    metrics.inc('ops.gather_fallbacks')
    return jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
  metrics.inc('ops.gather_runs')
  from ..utils.trace import record_dispatch
  t0 = time.perf_counter()
  record_dispatch('gather2')
  out = _gather_rows_hbm2_impl(table, ids, block_rows, run_span,
                               presorted, interpret)
  # dispatch clock, NOT device time (the call is asynchronous): useful
  # as a liveness/regression signal, never as a throughput claim
  metrics.observe('ops.gather_ms', (time.perf_counter() - t0) * 1e3)
  return out
