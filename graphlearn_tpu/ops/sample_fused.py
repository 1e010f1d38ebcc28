"""Fused sample+gather CSR hop: the neighbor-slot draw and the adjacency
gather in ONE Pallas pass.

The hardware-matched-sampler argument (GNNSampler, arxiv 2108.11571;
sampler accelerators, arxiv 2209.02916) instantiated for TPU: XLA lowers
``ops.uniform_sample``'s hop as (a) the [B, K] offset draw, (b) an
HBM-materialized [B, K] ``epos`` intermediate, and (c) a LATENCY-BOUND
element gather over the [E] CSR indices array — one DMA transaction per
sampled edge (~140M elem/s, PERF.md). But a seed's neighbor segment
``indices[start : start+deg]`` is CONTIGUOUS in HBM, so this kernel
stages it with ONE aligned multi-row DMA per seed and resolves all k
draws against the staged window with dense VPU one-hot selection —
k transactions collapse to ~1 for every seed whose segment fits the
window, and the sampled edges never round-trip through an
HBM-materialized intermediate.

Bit-matching contract: the draw itself (offsets, validity mask, epos)
is computed OUTSIDE the kernel with byte-for-byte the same jnp ops as
``ops.uniform_sample`` fed by the same counter-addressed fold_in key —
so the kernel's only job is ``indices[epos]``, and the off-TPU XLA
fallback IS ``ops.uniform_sample``'s stream: identical edges, identical
epos, identical mask, on every path. On TPU there is no fallback: a
caller that asked for the kernel gets it, or the compiler's refusal.

Layout: the CSR indices ship as a FILL-padded aligned ``[ceil(E/128),
128]`` block view (``build_indices128`` — the 128-lane cousin of block
sampling's [E/16, 16] view). Per seed the kernel branches:

  deg fits the window  -> one [NR, 128]-row DMA staging the aligned
                          superset of [start, start+deg) (NR =
                          window//128 + 1 covers any start alignment);
  deg > window (hubs)  -> k single-[128]-row DMAs, one per sampled
                          position — no worse than XLA's k element
                          transactions, and hop-local (no fallback
                          cliff: a single hub in the frontier does not
                          de-optimize the rest of the batch).

Routing is evidence-gated like every kernel in this repo:
``NeighborSampler(use_fused_hop=...)`` defaults to False, the XLA path
stays bit-identical, and interpret-mode parity tests pin the kernel
against ``ops.uniform_sample`` on CPU (tests/test_ops.py).
"""
import functools

import jax
import jax.numpy as jnp

from .neighbor import draw_offsets
from .unique import FILL

LANES = 128
WINDOW = 512   # default staged segment span per seed (sample_hop_fused)


def build_indices128(indices, min_rows: int = 0):
  """[E] CSR indices -> FILL-padded aligned [max(ceil(E/128), min_rows),
  128] view (device-side; a free reshape plus tail pad)."""
  e = int(indices.shape[0])
  rows = max(-(-e // LANES), min_rows, 1)
  pad = rows * LANES - e
  ind = jnp.asarray(indices).astype(jnp.int32)
  if pad:
    ind = jnp.concatenate([ind, jnp.full((pad,), FILL, jnp.int32)])
  return ind.reshape(rows, LANES)


def _draw(start, deg, seed_mask, k: int, key):
  """ops.uniform_sample's offset draw: the same key over the same shape
  through the same arithmetic (the bit-matching contract lives or dies
  on this)."""
  u = jax.random.uniform(key, (seed_mask.shape[0], k))
  return draw_offsets(start, deg, seed_mask, u, k)


def _use_kernel(name: str, blocks128, interpret: bool, force: bool) -> bool:
  """True -> run the Pallas kernel, False -> the off-TPU XLA twin. On
  TPU the kernel was asked for, so there is no XLA route: a missing
  aligned view raises here, and a kernel Mosaic refuses raises at
  compile time with Mosaic's message."""
  if interpret or force:
    if blocks128 is None:
      raise ValueError(f'{name}: interpret/force need the '
                       'build_indices128 view')
    return True
  if jax.default_backend() != 'tpu':
    return False
  if blocks128 is None:
    raise ValueError(f'{name}: called on TPU without the '
                     'build_indices128 aligned view — the fused kernel '
                     'cannot run and does not fall back to XLA on TPU; '
                     'call ops.uniform_sample for the XLA hop')
  return True


def _hop_kernel_factory(k, nr, kp):
  """Per grid step (``bs`` seeds): stage each seed's segment, then pick
  its k samples. Shaped by what Mosaic lowers on v5e (PERF.md
  "Bring-up"): scalars the DMAs are addressed by live in SMEM; both DMA
  paths of a seed share ONE semaphore (a seed takes exactly one path,
  and DMA semaphore memory holds ~512 of them); staging buffers are
  [row, seed, lane] so every vector read is a leading-dim index; and the
  selection is k unrolled [bs, 128] lane-select passes — no 3-D one-hot,
  no reshape, no unaligned concatenate."""
  def kernel(plan_s, hub_s, blocks_ref, erow_ref, lane_ref, row0_ref,
             small_ref, out_ref, win, big, sems):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bs = out_ref.shape[0]

    def window(s):
      return pltpu.make_async_copy(
          blocks_ref.at[pl.ds(plan_s[s * 8], nr)], win.at[:, s],
          sems.at[s])

    def hub_row(s, j):
      return pltpu.make_async_copy(blocks_ref.at[hub_s[s * kp + j]],
                                   big.at[j, s], sems.at[s])

    def each(s, fn):
      small = plan_s[s * 8 + 1]

      @pl.when(small == 1)
      def _():
        fn(window(s))

      @pl.when(small == 0)
      def _():
        for j in range(k):
          fn(hub_row(s, j))

    def issue(s, carry):
      each(s, lambda dma: dma.start())
      return carry

    def drain(s, carry):
      each(s, lambda dma: dma.wait())
      return carry

    jax.lax.fori_loop(0, bs, issue, None)
    jax.lax.fori_loop(0, bs, drain, None)

    rsel = erow_ref[:] - row0_ref[:]            # [bs, k] row in window
    lane = lane_ref[:]                          # [bs, k]
    small = small_ref[:] == 1                   # [bs, 1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bs, LANES), 1)
    out = jnp.zeros((bs, LANES), jnp.int32)
    for j in range(k):
      rj = rsel[:, j:j + 1]
      row = win[0]
      for r in range(1, nr):
        row = jnp.where(rj == r, win[r], row)
      # hub seeds staged sample j's own row; their window is untouched
      row = jnp.where(small, row, big[j])
      pick = jnp.sum(jnp.where(lanes == lane[:, j:j + 1], row, 0), axis=1,
                     keepdims=True)
      out = jnp.where(lanes == j, pick, out)
    out_ref[:] = out
  return kernel


def _gather_epos_pallas(blocks128, start, deg, safe_epos, k: int,
                        window: int, block_seeds: int, interpret: bool):
  """``indices[safe_epos]`` via per-seed staged windows (see module
  docstring); values at masked slots are whatever row 0 holds — callers
  mask them, exactly like the XLA path's ``indices[safe_epos]``."""
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  b = start.shape[0]
  assert window % LANES == 0 and window > 0
  nr = window // LANES + 1      # covers any start%128 alignment
  nbk = blocks128.shape[0]
  assert nbk >= nr, 'build_indices128(min_rows=nr) guarantees this'
  assert 0 < k <= LANES
  if interpret:
    bs = min(block_seeds, b)
  elif block_seeds % LANES:
    # the SMEM plan blocks are 1024-word tiles: 8 words per seed
    raise ValueError(f'block_seeds={block_seeds} must be a multiple of '
                     f'{LANES} for the compiled kernel')
  else:
    bs = block_seeds
  pad = (-b) % bs
  n = b + pad
  row0 = jnp.clip(start // LANES, 0, nbk - nr).astype(jnp.int32)
  # every sampled position of a 'small' seed lies inside its window:
  # epos < start + deg <= row0*128 + nr*128 (clamped row0 only lowers
  # the base, and the window top then reaches the padded array end)
  small = ((start - row0 * LANES + deg) <= nr * LANES).astype(jnp.int32)
  epos32 = safe_epos.astype(jnp.int32)
  if pad:   # pad seeds stage row 0 as 'small' seeds; sliced off below
    row0 = jnp.concatenate([row0, jnp.zeros((pad,), jnp.int32)])
    small = jnp.concatenate([small, jnp.ones((pad,), jnp.int32)])
    epos32 = jnp.concatenate([epos32, jnp.zeros((pad, k), jnp.int32)])
  erow = jnp.clip(epos32 // LANES, 0, nbk - 1)
  lane = epos32 % LANES
  # SMEM copies of what addresses the DMAs, flat and tile-aligned: 8
  # words per seed (row0, small), kp >= k words per seed (hub rows)
  kp = -(-k // 8) * 8
  plan = jnp.pad(jnp.stack([row0, small], axis=1),
                 ((0, 0), (0, 6))).reshape(-1)
  hub = jnp.pad(erow, ((0, 0), (0, kp - k))).reshape(-1)

  smem = lambda width: pl.BlockSpec((width,), lambda i: (i,),
                                    memory_space=pltpu.SMEM)
  vmem = lambda width: pl.BlockSpec((bs, width), lambda i: (i, 0))
  out = pl.pallas_call(
      _hop_kernel_factory(k, nr, kp),
      grid=(n // bs,),
      in_specs=[smem(bs * 8), smem(bs * kp),
                pl.BlockSpec(memory_space=pl.ANY),         # blocks128
                vmem(k), vmem(k), vmem(1), vmem(1)],
      out_specs=vmem(LANES),
      scratch_shapes=[pltpu.VMEM((nr, bs, LANES), jnp.int32),
                      pltpu.VMEM((k, bs, LANES), jnp.int32),
                      pltpu.SemaphoreType.DMA((bs,))],
      out_shape=jax.ShapeDtypeStruct((n, LANES), jnp.int32),
      interpret=interpret,
  )(plan, hub, blocks128, erow, lane, row0[:, None], small[:, None])
  return out[:b, :k]


@functools.partial(jax.jit,
                   static_argnames=('k', 'window', 'block_seeds',
                                    'interpret', 'force'))
def sample_hop_fused(indptr, indices, blocks128, seeds, seed_mask, k: int,
                     key, meta=None, window: int = WINDOW,
                     block_seeds: int = 128, interpret: bool = False,
                     force: bool = False):
  """One fused uniform CSR hop; same output contract — and the same
  PRNG stream, bit for bit — as :func:`ops.uniform_sample`.

  Args:
    indptr/indices: the CSR (used by the fallback path and for
      ``meta=None`` row lookup).
    blocks128: :func:`build_indices128` aligned view (None is only
      accepted off-TPU, where the XLA twin runs anyway).
    seeds/seed_mask/k/key/meta: exactly :func:`ops.uniform_sample`.
    window: staged segment span per seed (multiple of 128). Seeds with
      deg > window take the per-sample row-DMA path — never a
      whole-batch fallback.
    block_seeds: seeds per grid step.
    interpret: run the Pallas interpreter (CPU parity tests).
    force: run the kernel off-TPU (AOT lowering checks); default
      falls back to the XLA hop off-TPU.

  Returns (nbrs [B, K], epos [B, K], mask [B, K]) — FILL/0-padded like
  ``uniform_sample``.
  """
  safe_seeds = jnp.where(seed_mask, seeds, 0)
  if meta is not None:
    row = meta[safe_seeds]
    start, deg = row[:, 0], row[:, 1]
  else:
    start = indptr[safe_seeds]
    deg = indptr[safe_seeds + 1] - start
  epos, mask = _draw(start, deg, seed_mask, k, key)
  safe_epos = jnp.where(mask, epos, 0)
  if _use_kernel('sample_hop_fused', blocks128, interpret, force):
    picked = _gather_epos_pallas(blocks128, start, deg, safe_epos, k,
                                 window, block_seeds, interpret)
  else:
    picked = indices[safe_epos]
  nbrs = jnp.where(mask, picked, FILL)
  return nbrs, safe_epos, mask
