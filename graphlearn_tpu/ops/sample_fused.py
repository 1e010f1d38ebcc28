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

from .induce_merge import MergeInducerState, induce_next_merge
from .neighbor import draw_offsets
from .unique import FILL

LANES = 128

# fused-LEVEL kernel bound: the in-kernel dedup is O(S^2) value-compares
# (S = frontier * k candidates) — dense VPU work that beats the merge
# engine's sort cascade only while S^2 stays small. Past this bound the
# wrapper refuses at trace time; the tuner then scores the candidate as
# broken evidence instead of shipping a regression.
LEVEL_MAX_CANDIDATES = 1 << 15


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
                     key, meta=None, window: int = 512,
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
    window: staged segment span per seed (multiple of 128; an autotune
      axis of tune/tuner.py). Seeds with deg > window
      take the per-sample row-DMA path — never a whole-batch fallback.
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


def _chunk_of(n: int) -> int:
  """Largest inner-reduction tile (multiple of LANES, <= 1024) dividing
  ``n`` — bounds every [128, tile] compare transient to <=512KB VMEM."""
  for c in (1024, 512, 256, 128):
    if n % c == 0:
      return c
  raise AssertionError(f'{n} is not a multiple of {LANES}')


def _level_kernel_factory(k, nr, nbk, bs, n_gather, s_fill, s_buf, c_pad,
                          limit, limit_pad):
  """Whole-fanout-level kernel: grid steps [0, n_gather) stage per-seed
  CSR windows and resolve the k draws (the sample+gather phases, shared
  with the hop kernel); the FINAL grid step resolves the dedup map
  in-kernel — membership against the node-buffer prefix (a node's
  position in the buffer IS its local index), within-level first
  occurrence, and value-determined ranks that assign new locals in
  ascending-id order, reproducing ops.induce_next_merge's assignment
  exactly without a single sort."""
  cjs = _chunk_of(s_buf)
  cjc = _chunk_of(c_pad)

  def kernel(plan_ref, misc_ref, blocks_ref, epos_ref, mask_ref, meta_ref,
             nodes_ref, cols_ref, block_ref, counts_ref, win, big, flat,
             val, winr, rank, fnd_b, pos_b, sem_w, sem_b):
    from jax.experimental import pallas as pl
    i = pl.program_id(0)

    # ---- gather phase: one seed block per step (hop-kernel core) --------
    @pl.when(i < n_gather)
    def _gather():
      def dmas(s):
        from jax.experimental.pallas import tpu as pltpu
        row0 = plan_ref[i * bs + s, 0]
        small = plan_ref[i * bs + s, 1]
        window = pltpu.make_async_copy(blocks_ref.at[pl.ds(row0, nr)],
                                       win.at[s], sem_w.at[s])
        return small, window

      def row_dma(s, j):
        from jax.experimental.pallas import tpu as pltpu
        r = jnp.clip(epos_ref[s, j] // LANES, 0, nbk - 1)
        return pltpu.make_async_copy(blocks_ref.at[r], big.at[s, j],
                                     sem_b.at[s, j])

      def issue(s, carry):
        small, window = dmas(s)

        @pl.when(small == 1)
        def _():
          window.start()

        @pl.when(small == 0)
        def _():
          def issue_j(j, c):
            row_dma(s, j).start()
            return c
          jax.lax.fori_loop(0, k, issue_j, None, unroll=True)
        return carry

      jax.lax.fori_loop(0, bs, issue, None)

      def drain(s, carry):
        small, window = dmas(s)

        @pl.when(small == 1)
        def _():
          window.wait()

        @pl.when(small == 0)
        def _():
          def drain_j(j, c):
            row_dma(s, j).wait()
            return c
          jax.lax.fori_loop(0, k, drain_j, None, unroll=True)
        return carry

      jax.lax.fori_loop(0, bs, drain, None)

      # dense one-hot extraction over the staged windows — byte for byte
      # the hop kernel's epilogue
      epos = epos_ref[:]                               # [bs, k]
      row0 = meta_ref[:, 0]                            # [bs]
      small = meta_ref[:, 1]
      wflat = win[:].reshape(bs, nr * LANES)
      pos_l = jnp.clip(epos - row0[:, None] * LANES, 0, nr * LANES - 1)
      lanes_w = jax.lax.broadcasted_iota(jnp.int32, (1, 1, nr * LANES), 2)
      small_nbrs = jnp.sum(
          wflat[:, None, :] * (pos_l[:, :, None] == lanes_w), axis=-1)
      lanes_b = jax.lax.broadcasted_iota(jnp.int32, (1, 1, LANES), 2)
      big_nbrs = jnp.sum(big[:] * ((epos % LANES)[:, :, None] == lanes_b),
                         axis=-1)
      sel = jnp.where(small[:, None] == 1, small_nbrs, big_nbrs)  # [bs, k]
      base = i * (bs * k)
      flat[0, pl.ds(base, bs * k)] = sel.reshape(-1)
      val[0, pl.ds(base, bs * k)] = mask_ref[:].reshape(-1)

    # ---- dedup phase: the level's relabel map, in-register --------------
    @pl.when(i == n_gather)
    def _dedup():
      nn = misc_ref[0]                       # num_nodes before this level
      n_i = s_buf // LANES
      if s_buf > s_fill:
        # lane-alignment tail past the last written candidate: scratch is
        # uninitialized, so the validity flags there must be cleared
        # before any compare reads them
        val[0, pl.ds(s_fill, s_buf - s_fill)] = jnp.zeros(
            (s_buf - s_fill,), jnp.int32)

      def pass1(ci, carry):
        ds = pl.ds(ci * LANES, LANES)
        a = flat[0, ds].reshape(LANES, 1)
        av = val[0, ds].reshape(LANES, 1)
        apos = ci * LANES + jax.lax.broadcasted_iota(
            jnp.int32, (LANES, 1), 0)

        def memb(cj, acc):
          f2, p2 = acc
          ndc = nodes_ref[0, pl.ds(cj * cjc, cjc)].reshape(1, cjc)
          idc = cj * cjc + jax.lax.broadcasted_iota(
              jnp.int32, (1, cjc), 1)
          eq = ((a == ndc) & (idc < nn)).astype(jnp.int32)
          f2 = jnp.maximum(f2, jnp.max(eq, axis=1, keepdims=True))
          p2 = jnp.maximum(
              p2, jnp.max(jnp.where(eq > 0, idc, -1), axis=1,
                          keepdims=True))
          return f2, p2

        fnd, pos = jax.lax.fori_loop(
            0, c_pad // cjc, memb,
            (jnp.zeros((LANES, 1), jnp.int32),
             jnp.full((LANES, 1), -1, jnp.int32)))

        def dupl(cj, d):
          fc = flat[0, pl.ds(cj * cjs, cjs)].reshape(1, cjs)
          vc = val[0, pl.ds(cj * cjs, cjs)].reshape(1, cjs)
          pc = cj * cjs + jax.lax.broadcasted_iota(
              jnp.int32, (1, cjs), 1)
          hit = ((a == fc) & (vc > 0) & (pc < apos)).astype(jnp.int32)
          return jnp.maximum(d, jnp.max(hit, axis=1, keepdims=True))

        dup = jax.lax.fori_loop(0, s_buf // cjs, dupl,
                                jnp.zeros((LANES, 1), jnp.int32))
        winr[0, ds] = (av * (1 - fnd) * (1 - dup)).reshape(-1)
        fnd_b[0, ds] = fnd.reshape(-1)
        pos_b[0, ds] = pos.reshape(-1)
        return carry

      jax.lax.fori_loop(0, n_i, pass1, None)

      def pass2(ci, carry):
        ds = pl.ds(ci * LANES, LANES)
        a = flat[0, ds].reshape(LANES, 1)
        av = val[0, ds].reshape(LANES, 1)
        fnd = fnd_b[0, ds].reshape(LANES, 1)
        pos = pos_b[0, ds].reshape(LANES, 1)

        def rnk(cj, r):
          fc = flat[0, pl.ds(cj * cjs, cjs)].reshape(1, cjs)
          wc = winr[0, pl.ds(cj * cjs, cjs)].reshape(1, cjs)
          return r + jnp.sum(wc * (fc < a).astype(jnp.int32), axis=1,
                             keepdims=True)

        rk = jax.lax.fori_loop(0, s_buf // cjs, rnk,
                               jnp.zeros((LANES, 1), jnp.int32))
        rank[0, ds] = rk.reshape(-1)
        cols = jnp.where(fnd > 0, pos,
                         jnp.where(av > 0, nn + rk, -1))
        cols_ref[0, ds] = cols.reshape(-1)
        return carry

      jax.lax.fori_loop(0, n_i, pass2, None)

      num_new = jnp.sum(winr[0, :])
      num_kept = jnp.minimum(num_new, limit)
      counts_ref[0:1, :] = jnp.zeros((1, LANES), jnp.int32) + num_new

      def pass3(ri, carry):
        r = ri * LANES + jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)

        def bsel(cj, v):
          fc = flat[0, pl.ds(cj * cjs, cjs)].reshape(1, cjs)
          wc = winr[0, pl.ds(cj * cjs, cjs)].reshape(1, cjs)
          rc = rank[0, pl.ds(cj * cjs, cjs)].reshape(1, cjs)
          hit = wc * (rc == r).astype(jnp.int32)
          return v + jnp.sum(hit * fc, axis=1, keepdims=True)

        v = jax.lax.fori_loop(0, s_buf // cjs, bsel,
                              jnp.zeros((LANES, 1), jnp.int32))
        blk = jnp.where(r < num_kept, v, FILL)
        block_ref[0, pl.ds(ri * LANES, LANES)] = blk.reshape(-1)
        return carry

      jax.lax.fori_loop(0, limit_pad // LANES, pass3, None)

  return kernel


def _level_pallas(blocks128, start, deg, safe_epos, mask, nodes_prefix,
                  num_nodes, k: int, limit: int, window: int,
                  block_seeds: int, interpret: bool):
  """Run the fused level kernel. Returns (cols_raw [S], block
  [limit], num_new) — the relabel map (pre-truncation-mask), the
  ascending-id winner append block (FILL past num_kept), and the RAW
  new-unique count."""
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  b = start.shape[0]
  assert window % LANES == 0 and window > 0
  nr = window // LANES + 1
  nbk = blocks128.shape[0]
  assert nbk >= nr, 'build_indices128(min_rows=nr) guarantees this'
  assert 0 < k <= LANES
  bs = min(block_seeds, b)
  pad = (-b) % bs
  s_fill = (b + pad) * k
  s_buf = -(-s_fill // LANES) * LANES
  if s_buf > LEVEL_MAX_CANDIDATES:
    raise ValueError(
        f'fused level: {b} seeds x fanout {k} = {s_buf} padded candidates '
        f'exceeds LEVEL_MAX_CANDIDATES={LEVEL_MAX_CANDIDATES} (the '
        'in-kernel dedup is O(S^2) compares — route this plan through the '
        'hop kernel or the XLA merge engine instead)')
  c = nodes_prefix.shape[0]
  c_pad = -(-c // LANES) * LANES
  limit_pad = max(-(-limit // LANES) * LANES, LANES)

  row0 = jnp.clip(start // LANES, 0, nbk - nr).astype(jnp.int32)
  small = ((start - row0 * LANES + deg) <= nr * LANES).astype(jnp.int32)
  plan = jnp.stack([row0, small], axis=1)            # [b, 2]
  epos32 = safe_epos.astype(jnp.int32)
  mask32 = mask.astype(jnp.int32)
  if pad:
    plan = jnp.concatenate(
        [plan, jnp.tile(jnp.array([[0, 1]], jnp.int32), (pad, 1))])
    epos32 = jnp.concatenate([epos32, jnp.zeros((pad, k), jnp.int32)])
    mask32 = jnp.concatenate([mask32, jnp.zeros((pad, k), jnp.int32)])
  nodes_row = nodes_prefix.astype(jnp.int32).reshape(1, c)
  if c_pad > c:
    nodes_row = jnp.concatenate(
        [nodes_row, jnp.full((1, c_pad - c), FILL, jnp.int32)], axis=1)
  misc = jnp.asarray(num_nodes, jnp.int32).reshape(1)
  n_gather = (b + pad) // bs

  def gather_blk(i, plan_ref, misc_ref):
    return (jnp.minimum(i, n_gather - 1), 0)

  cols, block, counts = pl.pallas_call(
      _level_kernel_factory(k, nr, nbk, bs, n_gather, s_fill, s_buf,
                            c_pad, limit, limit_pad),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(n_gather + 1,),
          in_specs=[
              pl.BlockSpec(memory_space=pl.ANY),           # blocks128
              pl.BlockSpec((bs, k), gather_blk),           # epos
              pl.BlockSpec((bs, k), gather_blk),           # mask
              pl.BlockSpec((bs, 2), gather_blk),           # meta (= plan)
              pl.BlockSpec((1, c_pad), lambda *_: (0, 0)),  # node prefix
          ],
          out_specs=[
              pl.BlockSpec((1, s_buf), lambda *_: (0, 0)),
              pl.BlockSpec((1, limit_pad), lambda *_: (0, 0)),
              pl.BlockSpec((1, LANES), lambda *_: (0, 0)),
          ],
          scratch_shapes=[
              pltpu.VMEM((bs, nr, LANES), jnp.int32),      # win
              pltpu.VMEM((bs, k, LANES), jnp.int32),       # big
              pltpu.VMEM((1, s_buf), jnp.int32),          # flat
              pltpu.VMEM((1, s_buf), jnp.int32),          # val
              pltpu.VMEM((1, s_buf), jnp.int32),          # winner
              pltpu.VMEM((1, s_buf), jnp.int32),          # rank
              pltpu.VMEM((1, s_buf), jnp.int32),          # found
              pltpu.VMEM((1, s_buf), jnp.int32),          # pos
              pltpu.SemaphoreType.DMA((bs,)),
              pltpu.SemaphoreType.DMA((bs, k)),
          ],
      ),
      out_shape=[
          jax.ShapeDtypeStruct((1, s_buf), jnp.int32),
          jax.ShapeDtypeStruct((1, limit_pad), jnp.int32),
          jax.ShapeDtypeStruct((1, LANES), jnp.int32),
      ],
      interpret=interpret,
  )(plan, misc, blocks128, epos32, mask32, plan, nodes_row)
  return cols[0, :b * k], block[0, :limit], counts[0, 0]


@functools.partial(jax.jit,
                   static_argnames=('k', 'prefix_cap', 'max_new', 'final',
                                    'window', 'block_seeds', 'interpret',
                                    'force'))
def sample_level_fused(indptr, indices, blocks128, seeds, seed_mask,
                       k: int, key, state, src_idx, meta=None, *,
                       prefix_cap: int, max_new=None, final: bool = False,
                       window: int = 512, block_seeds: int = 128,
                       interpret: bool = False, force: bool = False):
  """One whole fanout LEVEL — sample + gather + exact cross-hop dedup —
  in a single fused kernel pass, bit-identical to ``ops.uniform_sample``
  followed by :func:`ops.induce_next_merge`.

  The draw (offsets, mask, epos) stays OUTSIDE the kernel, byte for byte
  ``ops.uniform_sample``'s stream off the same counter-addressed key —
  the kernel resolves ``indices[epos]`` via staged windows (the hop
  kernel's phases) and then the dedup map in the same pass: membership
  against the node-buffer prefix (a node's buffer position IS its local
  index), within-level first occurrence, and value-determined ranks
  (``rank(v) = #{winner values < v}``) that assign new locals in
  ascending-id order — exactly the merge engine's sorted-rank
  assignment, duplicates sharing their winner's local by construction,
  with no sort anywhere in the kernel.

  Args:
    indptr/indices/blocks128/seeds/seed_mask/k/key/meta/window/
    block_seeds/interpret/force: as :func:`sample_hop_fused` (``seeds``
    is this level's frontier).
    state: the :class:`ops.MergeInducerState` before this level. The
      kernel path leaves the sorted view STALE (it never reads it);
      the XLA fallback maintains it (``update_view=not final``) so
      off-TPU programs remain bit-identical to the unfused engine.
    src_idx: frontier local indices (edge source relabel).
    prefix_cap: static occupancy bound before this level (the merge
      layout offset — bounds the in-kernel membership scan).
    max_new: static clamp on nodes kept (the plan's next-hop cap).
    final: last level induced on this state (fallback skips its view
      rebuild, exactly like the unfused engine's ``final`` hop).

  Returns ``(state', out, epos, mask)`` with ``out`` the
  ``induce_next_merge`` output dict.
  """
  f = seeds.shape[0]
  size = f * k
  cap = state.nodes.shape[0]
  c = min(prefix_cap, cap)
  limit = min(size, cap - c, size if max_new is None else max_new)

  safe_seeds = jnp.where(seed_mask, seeds, 0)
  if meta is not None:
    row = meta[safe_seeds]
    start, deg = row[:, 0], row[:, 1]
  else:
    start = indptr[safe_seeds]
    deg = indptr[safe_seeds + 1] - start
  epos, mask = _draw(start, deg, seed_mask, k, key)
  safe_epos = jnp.where(mask, epos, 0)

  if not _use_kernel('sample_level_fused', blocks128, interpret, force):
    picked = indices[safe_epos]
    nbrs = jnp.where(mask, picked, FILL)
    state2, out = induce_next_merge(state, src_idx, nbrs, mask,
                                    prefix_cap=prefix_cap, max_new=max_new,
                                    update_view=not final)
    return state2, out, safe_epos, mask

  nodes_prefix = jax.lax.slice(state.nodes, (0,), (c,))
  cols_raw, block, num_new = _level_pallas(
      blocks128, start, deg, safe_epos, mask, nodes_prefix,
      state.num_nodes, k, limit, window, block_seeds, interpret)
  num_new = num_new.astype(jnp.int32)
  num_kept = jnp.minimum(num_new, limit)

  flat_mask = mask.reshape(-1)
  emask = flat_mask & (cols_raw >= 0) & \
      (cols_raw < state.num_nodes + num_kept)
  cols = jnp.where(emask, cols_raw, -1)
  rows = jnp.where(emask, jnp.repeat(src_idx.astype(jnp.int32), k), -1)

  block = block.astype(state.nodes.dtype)
  nodes = jax.lax.dynamic_update_slice(state.nodes, block,
                                       (state.num_nodes,))
  frontier = jnp.concatenate(
      [block, jnp.full((size - limit,), FILL, block.dtype)]) \
      if limit < size else block
  fin = jnp.arange(size) < num_kept
  frontier_idx = jnp.where(
      fin, state.num_nodes + jnp.arange(size, dtype=jnp.int32), -1)

  out = dict(rows=rows, cols=cols, edge_mask=emask, frontier=frontier,
             frontier_idx=frontier_idx, frontier_mask=fin,
             num_new=num_new)
  state2 = MergeInducerState(nodes, state.num_nodes + num_kept,
                             state.sorted_ids, state.sorted_loc)
  return state2, out, safe_epos, mask
