"""Fixed-shape neighbor sampling over an HBM-resident CSR.

TPU-native replacement for the reference CUDA sampler
(/root/reference/graphlearn_torch/csrc/cuda/random_sampler.cu). The CUDA path
computes exact per-seed neighbor counts, a prefix sum, a D2H sync, and a
variable-size output (random_sampler.cu:267-307); on TPU that sync and dynamic
shape would break jit, so sampling emits a dense ``[B, K]`` buffer with a
validity mask:

  deg <= K: take all neighbors in order (mask pads the tail) — matches the
            reference's "keep all" branch.
  deg >  K: K uniform draws with replacement (matches the reference CPU
            sampler semantics, csrc/cpu/random_sampler.cc:24-47; the CUDA
            reservoir's without-replacement guarantee is relaxed — tests, like
            the reference's, assert membership/caps, not exact multisets).

Weighted sampling follows the reference CPU weighted sampler's CDF + binary
search (csrc/cpu/weighted_sampler.cc:147-193) but over a precomputed per-row
cumulative-weight array so the per-draw work is a fixed 32-step bisection.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics.registry_names import SCOPE_ROWS, SCOPE_TILE
from .sorted_index import indexed_membership
from .unique import FILL


# A frontier of at least _MIN_TILED_ROWS rows is drawn tile by tile, and only
# in the tiles that begin below its last valid row: calibrated frontier caps
# leave a third of every hop padding, and on the v5e a padded row's gathers
# (all of element 0) cost more than a valid row's (PERF.md, PR 27). Up to
# DRAW_TILES tiles, of a multiple of _TILE_ALIGN rows: finer tiles leave
# less padding in the last one run, and below 256 rows a tile no longer
# pays for its loop iteration. Smaller frontiers (a seed batch, a serving
# call) take one gather over the whole cap.
DRAW_TILES = 64
_TILE_ALIGN = 256
_MIN_TILED_ROWS = 2048


def draw_tile_rows(b: int) -> int:
  """Rows per tile for a frontier of ``b`` rows (a multiple of
  ``_TILE_ALIGN``), or 0 where the frontier is too small to tile."""
  if b < _MIN_TILED_ROWS:
    return 0
  per_tile = -(-b // DRAW_TILES)
  return -(-per_tile // _TILE_ALIGN) * _TILE_ALIGN


def tiles_to_run(mask, tile: int):
  """How many tiles of ``tile`` positions along the last axis of ``mask``
  begin below its last valid one: ``ceil(last valid position / tile)``
  as an int32 scalar, 0 for an empty mask. The trip count of every loop
  tiled by :func:`draw_tile_rows` (the draws', over a frontier or a
  received ``[P, cap]`` block; the mesh feature store's bounded lookup of
  such a block)."""
  n = mask.shape[-1]
  last_valid = jnp.max(jnp.where(
      mask, jnp.arange(1, n + 1, dtype=jnp.int32), 0))
  return (last_valid + (tile - 1)) // tile


def draw_offsets(start, deg, seed_mask, u, k: int):
  """The uniform draw's arithmetic, shared with ``ops/sample_fused.py``
  (whose kernels promise this stream bit for bit): per-row (start, degree)
  and the ``[B, K]`` uniforms -> (epos, mask), unmasked ``epos``."""
  rand_off = jnp.floor(u * deg[:, None].astype(u.dtype)).astype(jnp.int32)
  rand_off = jnp.minimum(rand_off, jnp.maximum(deg[:, None] - 1, 0))
  seq_off = jnp.arange(k, dtype=jnp.int32)[None, :]
  offsets = jnp.where(deg[:, None] > k, rand_off, seq_off)
  mask = seed_mask[:, None] & (offsets < deg[:, None])
  return start[:, None] + offsets, mask


def _draw_rows(indptr, indices, meta, seeds, seed_mask, u, k: int):
  """``uniform_sample`` over the rows given (the whole frontier or one
  tile of it): the row-table gather, the offsets, the element gather."""
  safe_seeds = jnp.where(seed_mask, seeds, 0)
  if meta is not None:
    row = meta[safe_seeds]
    start, deg = row[:, 0], row[:, 1]
  else:
    start = indptr[safe_seeds]
    deg = indptr[safe_seeds + 1] - start
  epos, mask = draw_offsets(start, deg, seed_mask, u, k)
  safe_epos = jnp.where(mask, epos, 0)
  nbrs = jnp.where(mask, indices[safe_epos], FILL)
  return nbrs, safe_epos, mask


def draw_tiled(draw, seeds, seed_mask, u, tile: int):
  """``draw(seeds [n], seed_mask [n], u [n, K]) -> (nbrs, epos, mask)``
  (each ``[n, K]``) over ``seeds`` — a frontier ``[B]``, or a received
  request block ``[P, cap]`` whose rows are drawn together — in tiles of
  ``tile`` positions along the last axis, run only for the tiles that
  begin below the last valid position of ``seed_mask`` (any mask: a
  prefix skips its padding, a scattered one runs every tile). ``u`` is
  the whole input's ``[seeds.size, K]`` uniforms in row-major order,
  drawn once and sliced per tile, so every position draws what one piece
  would. Positions of tiles not run read what masked ones read: FILL, 0,
  False. The last tile is clamped to end at the last position, so it may
  redraw positions of the one before — to the same values. The trip
  count is this shard's own: no collective may sit inside ``draw``.

  Returns ``(nbrs, epos, mask, tiles)``, the first three ``[seeds.size,
  K]``; ``tiles`` is the int32 number of tiles run, ``ceil(last valid
  position / tile)``.
  """
  n, k = seeds.shape[-1], u.shape[-1]
  lead, ax = seeds.shape[:-1], seeds.ndim - 1
  assert 0 < tile <= n, (tile, n)
  u = u.reshape(lead + (n, k))
  tiles = tiles_to_run(seed_mask, tile)

  def body(i, out):
    with jax.named_scope(SCOPE_TILE):
      lo = jnp.minimum(i * tile, n - tile)
      cut = lambda x: jax.lax.dynamic_slice_in_dim(x, lo, tile, ax)
      part = draw(cut(seeds).reshape(-1), cut(seed_mask).reshape(-1),
                  cut(u).reshape(-1, k))
      return tuple(jax.lax.dynamic_update_slice_in_dim(
          o, p.reshape(lead + (tile, k)), lo, ax) for o, p in zip(out, part))

  shapes = jax.eval_shape(draw, seeds.reshape(-1), seed_mask.reshape(-1),
                          u.reshape(-1, k))
  # inside shard_map the carry varies over the axes the block does
  vma = tuple(jax.typeof(seeds).vma)
  init = tuple(jax.lax.pcast(jnp.full(lead + (n, k), fill, s.dtype), vma,
                             to='varying')
               for s, fill in zip(shapes, (FILL, 0, False)))
  out = jax.lax.fori_loop(0, tiles, body, init)
  return tuple(o.reshape(-1, k) for o in out) + (tiles,)


def uniform_sample_tiled(indptr, indices, seeds, seed_mask, k: int, key,
                         meta=None):
  """:func:`uniform_sample` in tiles of :func:`draw_tile_rows` frontier
  rows (one tile where that is 0) by :func:`draw_tiled`.

  Returns ``(nbrs, epos, mask, tiles)``; ``tiles`` is the int32 number of
  tiles run, ``ceil(last valid row / tile rows)``.
  """
  b = seeds.shape[0]
  u = jax.random.uniform(key, (b, k))    # ONE stream over the whole cap
  return draw_tiled(
      lambda s, m, u: _draw_rows(indptr, indices, meta, s, m, u, k),
      seeds, seed_mask, u, draw_tile_rows(b) or b)


@functools.partial(jax.jit, static_argnames=('k',))
def uniform_sample(indptr, indices, seeds, seed_mask, k: int, key,
                   meta=None):
  """Sample up to ``k`` neighbors per seed.

  Args:
    indptr:  [N+1] CSR row pointer (int32/int64, device-resident).
    indices: [E] neighbor ids.
    seeds:   [B] seed ids (padded entries arbitrary where ``seed_mask`` False).
    seed_mask: [B] bool validity.
    k: fanout (static).
    key: jax PRNG key.
    meta: optional [N, 2] (start, degree) row table
      (``build_csr_meta``). Folds the two indptr ELEMENT gathers into
      one ROW gather — on TPU both cost ~one HBM transaction per seed,
      so this halves the row-pointer lookup time (the same trick block
      mode uses for its metadata).

  Returns:
    nbrs:  [B, K] neighbor ids, FILL where invalid.
    epos:  [B, K] position into the CSR ``indices`` array of each sampled
           edge (valid where mask; use to gather edge ids/weights).
    mask:  [B, K] bool validity.

  A frontier large enough to tile (:func:`draw_tile_rows`) skips the
  gathers of its trailing padding (:func:`uniform_sample_tiled`); the
  outputs are the same in every element either way.
  """
  b = seeds.shape[0]
  if draw_tile_rows(b):
    return uniform_sample_tiled(indptr, indices, seeds, seed_mask, k, key,
                                meta)[:3]
  u = jax.random.uniform(key, (b, k))
  return _draw_rows(indptr, indices, meta, seeds, seed_mask, u, k)


def build_row_cumsum(indptr, weights):
  """Host/device precompute for weighted sampling: per-edge cumulative weight
  restarting at each row (so ``cum[indptr[r]:indptr[r+1]]`` is the row CDF)."""
  cum = jnp.cumsum(weights)
  row_base = jnp.concatenate([jnp.zeros((1,), cum.dtype), cum])[indptr[:-1]]
  n = indptr.shape[0] - 1
  counts = indptr[1:] - indptr[:-1]
  base_per_edge = jnp.repeat(row_base, counts,
                             total_repeat_length=weights.shape[0])
  return cum - base_per_edge


@functools.partial(jax.jit, static_argnames=('k',))
def weighted_sample(indptr, indices, row_cumsum, seeds, seed_mask, k: int,
                    key):
  """Edge-weight-biased sampling with replacement via inverse-CDF bisection.

  ``row_cumsum`` comes from :func:`build_row_cumsum`. Same output contract as
  :func:`uniform_sample`. Rows with degree <= k keep all neighbors (parity
  with the uniform path and the reference's keep-all branch).
  """
  b = seeds.shape[0]
  safe_seeds = jnp.where(seed_mask, seeds, 0)
  start = indptr[safe_seeds]
  end = indptr[safe_seeds + 1]
  deg = end - start
  total = row_cumsum[jnp.maximum(end - 1, 0)]
  total = jnp.where(deg > 0, total, 1.0)
  u = jax.random.uniform(key, (b, k)) * total[:, None]

  # Vectorized bisection for the first edge position with cum >= u within
  # [start, end). 32 steps cover any degree < 2^32.
  lo = jnp.broadcast_to(start[:, None], (b, k))
  hi = jnp.broadcast_to(end[:, None], (b, k))

  def body(_, carry):
    lo, hi = carry
    mid = (lo + hi) // 2
    go_right = row_cumsum[jnp.clip(mid, 0, row_cumsum.shape[0] - 1)] < u
    lo = jnp.where(go_right, mid + 1, lo)
    hi = jnp.where(go_right, hi, mid)
    return lo, hi

  lo, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
  wpos = jnp.minimum(lo, jnp.maximum(end[:, None] - 1, 0))

  seq_off = jnp.arange(k, dtype=start.dtype)[None, :]
  epos = jnp.where(deg[:, None] > k, wpos, start[:, None] + seq_off)
  mask = seed_mask[:, None] & (
      jnp.where(deg[:, None] > k, 0, seq_off) < deg[:, None])
  safe_epos = jnp.where(mask, epos, 0)
  nbrs = jnp.where(mask, indices[safe_epos], FILL)
  return nbrs, jnp.where(mask, epos, 0), mask


def choose_padded_window(fanouts, candidates=(16, 64, 128)) -> int:
  """Pick the padded-adjacency window for a fanout list.

  The window must cover max(fanout) (smaller would systematically
  under-sample). Among sufficient widths the measured order on v5e is
  16 > 64 > 128 >> 32 (PERF.md: W=32 hits a reproducible XLA
  tiling/codegen cliff — 10.0 ms vs 4.97 at W=16 and 6.52 at W=64 — so
  it is deliberately absent from ``candidates``).
  """
  need = max(fanouts)
  for w in candidates:
    if w >= need:
      return w
  return _round_up_pow2(need)


def _round_up_pow2(n: int) -> int:
  w = 1
  while w < n:
    w *= 2
  return w


def padded_table_stats(indptr, window: int):
  """Degree-conditional neighbor-recall of a [N, window] padded table.

  Quantifies the padded mode's disclosed truncation: rows with
  deg > window expose only a random ``window``-subset per epoch.
  Returns:
    node_recall: mean over nodes of min(deg, W)/deg (deg > 0).
    edge_recall: sum(min(deg, W)) / sum(deg) — the probability that a
      uniformly chosen EDGE's slot survives truncation; hub-sensitive,
      so it is the number that matters on power-law graphs.
    frac_truncated_nodes / frac_truncated_edges: how much of the graph
      the trade touches.
    recall_by_degree: {decile upper bound -> mean node recall} over
      degree deciles (only nodes with deg > 0).
  """
  indptr = np.asarray(indptr)
  deg = np.diff(indptr).astype(np.int64)
  pos = deg[deg > 0]
  kept = np.minimum(pos, window)
  stats = {
      'window': int(window),
      'node_recall': float((kept / pos).mean()) if pos.size else 1.0,
      'edge_recall': float(kept.sum() / max(pos.sum(), 1)),
      'frac_truncated_nodes': float((pos > window).mean()) if pos.size
      else 0.0,
      'frac_truncated_edges': float(pos[pos > window].sum()
                                    / max(pos.sum(), 1)),
  }
  if pos.size:
    qs = np.quantile(pos, np.linspace(0.1, 1.0, 10))
    by_dec = {}
    lo = 0
    for q in qs:
      sel = (pos > lo) & (pos <= q)
      if sel.any():
        by_dec[int(q)] = float((kept[sel] / pos[sel]).mean())
      lo = q
    stats['recall_by_degree'] = by_dec
  return stats


def build_padded_adjacency(indptr, indices, window: int, seed: int = 0,
                           edge_pos: bool = False):
  """Host-side: dense [N, window] neighbor table with per-row shuffling.

  The TPU answer to CSR pointer-chasing: XLA's ELEMENT gather over a
  [25M] CSR indices array is DMA-latency-bound (~120M elem/s,
  device-trace evidence in PERF.md), while ROW gathers move ~5x more
  bytes/s. This table makes a sampling hop one row gather + cheap
  in-row VPU selection. Rows with deg > window keep a uniformly random
  ``window``-subset (the shuffle makes the truncation unbiased; rebuild
  with a new seed to refresh the subset across epochs).

  Returns (nbr_table [N, window] int32, FILL-padded; deg [N] int32 =
  min(true degree, window); epos_table [N, window] or None — CSR edge
  positions for with_edge/weighted lookups).
  """
  indptr = np.asarray(indptr)
  indices = np.asarray(indices)
  n = indptr.shape[0] - 1
  e = indices.shape[0]
  rng = np.random.default_rng(seed)
  rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
  order = np.lexsort((rng.random(e), rows))     # shuffle within each row
  # `order` keeps row blocks contiguous, so the within-row rank after the
  # shuffle is the same arithmetic as before it
  shuf_rows = rows[order]
  shuf_within = np.arange(e, dtype=np.int64) - np.repeat(
      indptr[:-1], np.diff(indptr))
  sel = shuf_within < window
  tab = np.full((n, window), FILL, np.int32)
  tab[shuf_rows[sel], shuf_within[sel]] = indices[order][sel]
  deg = np.minimum(np.diff(indptr), window).astype(np.int32)
  epos = None
  if edge_pos:
    epos = np.zeros((n, window), np.int32)
    epos[shuf_rows[sel], shuf_within[sel]] = order[sel]
  return tab, deg, epos


@functools.partial(jax.jit, static_argnames=('window', 'edge_pos'))
def build_padded_adjacency_device(indptr, indices, window: int, key,
                                  edge_pos: bool = False):
  """Device-side :func:`build_padded_adjacency`: the same per-row
  shuffle + truncate construction as ONE two-key sort over the edge
  list plus a fixed-shape scatter — no host work, no [N, W] upload.

  Why it exists: the per-epoch padded reseed (de-biasing the deg > W
  truncation) cost ~90 s/epoch of HOST numpy + transfer at products
  scale (round-4 matrix finding); on device the rebuild is a ~E-entry
  sort + scatter (~0.5 s at 61M edges). Returns the same
  (tab, deg, epos) contract; subsets are exact uniform
  without-replacement per row, drawn from ``key``.
  """
  e = indices.shape[0]
  n = indptr.shape[0] - 1
  rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32),
                    jnp.diff(indptr).astype(jnp.int32),
                    total_repeat_length=e)
  rand = jax.random.uniform(key, (e,))
  # two-key sort keeps row blocks contiguous and shuffles within rows;
  # payload = original edge position
  _, _, order = jax.lax.sort(
      (rows, rand, jnp.arange(e, dtype=jnp.int32)), num_keys=2)
  within = jnp.arange(e, dtype=jnp.int32) - jnp.repeat(
      indptr[:-1].astype(jnp.int32), jnp.diff(indptr).astype(jnp.int32),
      total_repeat_length=e)
  # positions beyond the window scatter out of bounds -> dropped
  tab = jnp.full((n, window), FILL, jnp.int32)
  tab = tab.at[rows, within].set(indices[order].astype(jnp.int32),
                                 mode='drop')
  deg = jnp.minimum(jnp.diff(indptr), window).astype(jnp.int32)
  epos = None
  if edge_pos:
    epos = jnp.zeros((n, window), jnp.int32).at[rows, within].set(
        order, mode='drop')
  return tab, deg, epos


@functools.partial(jax.jit, static_argnames=('k',))
def uniform_sample_padded(nbr_table, deg, seeds, seed_mask, k: int, key,
                          epos_table=None):
  """Uniform fanout sampling over a padded adjacency table
  (:func:`build_padded_adjacency`). Same output contract as
  :func:`uniform_sample`; ``epos`` is only meaningful when
  ``epos_table`` is given (else zeros)."""
  b = seeds.shape[0]
  safe = jnp.where(seed_mask, seeds, 0)
  rows = nbr_table[safe]                          # [B, W] row gather
  d = jnp.where(seed_mask, deg[safe], 0)
  u = jax.random.uniform(key, (b, k))
  rand_off = jnp.floor(u * d[:, None].astype(u.dtype)).astype(jnp.int32)
  rand_off = jnp.minimum(rand_off, jnp.maximum(d[:, None] - 1, 0))
  seq_off = jnp.arange(k, dtype=jnp.int32)[None, :]
  offsets = jnp.where(d[:, None] > k, rand_off, seq_off)
  mask = seed_mask[:, None] & (offsets < d[:, None])
  safe_off = jnp.where(mask, offsets, 0)
  # in-row selection via one-hot contraction, NOT take_along_axis: a
  # dynamic axis-1 gather lowers to the same latency-bound element
  # gather this op exists to avoid; the one-hot multiply-sum is pure
  # VPU work over the already-gathered [B, W] rows
  onehot = (safe_off[:, :, None] ==
            jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None, :])
  picked = jnp.sum(rows[:, None, :] * onehot, axis=-1)
  nbrs = jnp.where(mask, picked, FILL)
  if epos_table is not None:
    ep = jnp.sum(epos_table[safe][:, None, :] * onehot, axis=-1)
    epos = jnp.where(mask, ep, 0)
  else:
    epos = jnp.zeros_like(nbrs)
  return nbrs, epos, mask


BLOCK = 16  # aligned CSR block width for block sampling


@functools.partial(jax.jit, static_argnames=('k',))
def uniform_sample_block(csr_meta, indices_blocks, num_edges: int, seeds,
                         seed_mask, k: int, key):
  """Block (cluster) fanout sampling over the raw CSR — row-gather speed
  without a prebuilt table.

  Element gathers over the CSR indices array are DMA-latency-bound on
  TPU, but 2-D ROW gathers run ~5x faster (PERF.md). This op reshapes
  the indices array into aligned [E/16, 16] blocks (``indices_blocks``,
  a free reshape of the padded array), draws ONE uniform position
  p = start + U[0, deg) per seed, gathers the single block containing p,
  and then draws the k samples uniformly from the block's elements that
  belong to the seed's segment. Marginals are EXACTLY uniform
  (P(block) * P(elem | block) = valid/deg * 1/valid = 1/deg); draws
  within one row of one hop are correlated through the shared block —
  cluster sampling, fresh per batch via the PRNG (unlike the padded
  table's fixed W-subset).

  ``csr_meta`` is the [N, 2] packed (row start, degree) table;
  ``indices_blocks`` is ``padded_indices.reshape(-1, 16)`` where the
  indices array is FILL-padded to a multiple of 16 (`num_edges` = true
  edge count). Same output contract as :func:`uniform_sample`.
  """
  assert k <= BLOCK, 'block sampling supports fanouts up to BLOCK=16'
  b = seeds.shape[0]
  nblocks = indices_blocks.shape[0]
  safe = jnp.where(seed_mask, seeds, 0)
  # (start, deg) packed per node: ONE 2-wide row gather instead of two
  # element gathers over indptr (element gathers are the latency-bound
  # op this mode exists to avoid)
  meta = csr_meta[safe]
  start = meta[:, 0]
  deg = jnp.where(seed_mask, meta[:, 1], 0)
  small = deg <= k                                 # keep-all branch
  ku, kk = jax.random.split(key)
  u = jax.random.uniform(ku, (b,))
  p = start + jnp.minimum((u * deg.astype(u.dtype)).astype(jnp.int32),
                          jnp.maximum(deg - 1, 0))
  # block anchor: the drawn position's block for sampled rows, the
  # segment's first block for keep-all rows (whose k slots may straddle
  # into the NEXT block — covered by a second row gather below)
  blk = jnp.clip(jnp.where(small, start // BLOCK, p // BLOCK), 0,
                 nblocks - 1)
  blk_base = blk * BLOCK
  rows = indices_blocks[blk]                       # [B, 16] row gather
  rows2 = indices_blocks[jnp.clip(blk + 1, 0, nblocks - 1)]
  lo = jnp.maximum(start, blk_base) - blk_base     # valid in-block range
  hi = jnp.minimum(start + deg, blk_base + BLOCK) - blk_base
  width = jnp.maximum(hi - lo, 0)
  u2 = jax.random.uniform(kk, (b, k))
  off_rand = lo[:, None] + jnp.minimum(
      (u2 * width[:, None].astype(u2.dtype)).astype(jnp.int32),
      jnp.maximum(width[:, None] - 1, 0))
  seq = jnp.arange(k, dtype=jnp.int32)[None, :]
  off = jnp.where(small[:, None],
                  (start - blk_base)[:, None] + seq, off_rand)
  mask = seed_mask[:, None] & jnp.where(
      small[:, None], seq < deg[:, None], width[:, None] > 0)
  # off in [0, 2*BLOCK): pick from the anchor block or its successor
  lanes = jnp.arange(BLOCK, dtype=jnp.int32)[None, None, :]
  pick_cur = jnp.sum(rows[:, None, :] * (off[:, :, None] == lanes),
                     axis=-1)
  pick_next = jnp.sum(
      rows2[:, None, :] * ((off[:, :, None] - BLOCK) == lanes), axis=-1)
  picked = jnp.where(off < BLOCK, pick_cur, pick_next)
  epos = jnp.where(mask, blk_base[:, None] + off, 0)
  epos = jnp.minimum(epos, num_edges - 1)
  nbrs = jnp.where(mask, picked, FILL)
  return nbrs, epos, mask


def _local_rows(row_ids, starts, seeds, seed_mask, shift: int, depth: int):
  """Shard-local row lookup ``global id -> position in row_ids`` through
  the table's two-level index (ops/sorted_index.py: one read of the
  bucket starts, then ``depth`` halvings inside the bucket), and which
  seeds this shard owns: the part of a draw's scope that is not the draw
  proper (``glt.sample/hop<h>/draw/.../rows``)."""
  with jax.named_scope(SCOPE_ROWS):
    found, pos = indexed_membership(row_ids, starts, seeds, shift, depth)
    return found & seed_mask, pos


def _draw_block(draw, seeds, seed_mask, k: int, key):
  """A shard-local draw over a received request block ``seeds [P, cap]``
  (a 1-D vector is a block of one row): in tiles of
  :func:`draw_tile_rows` ``(cap)`` columns by :func:`draw_tiled`, only
  below the block's last valid column, or in one piece where that is 0.
  ``ops.route_slots`` ranks a request within its destination, so every
  received bucket is a valid prefix followed by the pad -1, and a bucket
  sized to be loss-free is mostly pads. The outputs are ``[P·cap, K]``,
  one piece's element for element."""
  u = jax.random.uniform(key, (seeds.size, k))   # ONE stream over the block
  tile = draw_tile_rows(seeds.shape[-1])
  if not tile:
    return draw(seeds.reshape(-1), seed_mask.reshape(-1), u)
  return draw_tiled(draw, seeds, seed_mask, u, tile)[:3]


@functools.partial(jax.jit, static_argnames=('k', 'shift', 'depth'))
def uniform_sample_local(row_ids, indptr_loc, indices, seeds, seed_mask,
                         k: int, key, starts, shift: int, depth: int):
  """Uniform fanout sampling over a *partition-local* CSR.

  The distributed graph stores only owned rows per shard: ``row_ids`` is the
  ascending (INT_MAX-padded) list of owned global ids and ``indptr_loc``
  their local CSR offsets. Row lookup goes through the graph's two-level
  index over ``row_ids`` (``starts`` and its statics ``shift`` / ``depth``:
  ``DistGraph.row_index``) instead of direct indexing — the TPU replacement
  for the reference's partition-local Graph rows (csrc/cpu/graph.cc +
  dist_neighbor_sampler.py:624). Seeds not owned by this shard come back
  masked out.

  ``seeds`` / ``seed_mask`` are the received request block ``[P, cap]``
  (or a vector), drawn tile by tile below its last valid column
  (:func:`_draw_block`). Same output contract as :func:`uniform_sample`,
  over the ``P·cap`` slots in row-major order.
  """
  def draw(seeds, seed_mask, u):
    found, pos = _local_rows(row_ids, starts, seeds, seed_mask, shift, depth)
    start = indptr_loc[pos]
    deg = jnp.where(found, indptr_loc[pos + 1] - start, 0)
    epos, mask = draw_offsets(start, deg, found, u, k)
    safe_epos = jnp.where(mask, epos, 0)
    return jnp.where(mask, indices[safe_epos], FILL), safe_epos, mask

  return _draw_block(draw, seeds, seed_mask, k, key)


@functools.partial(jax.jit, static_argnames=('k', 'shift', 'depth'))
def weighted_sample_local(row_ids, indptr_loc, indices, row_cumsum, seeds,
                          seed_mask, k: int, key, starts, shift: int,
                          depth: int):
  """Edge-weight-biased fanout sampling over a *partition-local* CSR.

  Distributed counterpart of :func:`weighted_sample` (the reference's GPU
  path falls back to uniform for distributed weighted sampling,
  sampler/neighbor_sampler.py:86-91 — here the weighted path works in the
  sharded engine too). ``row_cumsum`` is the per-shard row-restarting
  cumulative weight array (:func:`build_row_cumsum` over the local CSR).
  Same output contract, the same row lookup and the same tiles as
  :func:`uniform_sample_local`.
  """
  def draw(seeds, seed_mask, u):
    b = seeds.shape[0]
    found, pos = _local_rows(row_ids, starts, seeds, seed_mask, shift, depth)
    start = indptr_loc[pos]
    end = indptr_loc[pos + 1]
    deg = jnp.where(found, end - start, 0)
    end = start + deg
    total = row_cumsum[jnp.maximum(end - 1, 0)]
    total = jnp.where(deg > 0, total, 1.0)
    u = u * total[:, None]

    lo = jnp.broadcast_to(start[:, None], (b, k))
    hi = jnp.broadcast_to(end[:, None], (b, k))

    def body(_, carry):
      lo, hi = carry
      mid = (lo + hi) // 2
      go_right = row_cumsum[jnp.clip(mid, 0, row_cumsum.shape[0] - 1)] < u
      lo = jnp.where(go_right, mid + 1, lo)
      hi = jnp.where(go_right, hi, mid)
      return lo, hi

    lo, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
    wpos = jnp.minimum(lo, jnp.maximum(end[:, None] - 1, 0))

    seq_off = jnp.arange(k, dtype=start.dtype)[None, :]
    epos = jnp.where(deg[:, None] > k, wpos, start[:, None] + seq_off)
    mask = found[:, None] & (
        jnp.where(deg[:, None] > k, 0, seq_off) < deg[:, None])
    safe_epos = jnp.where(mask, epos, 0)
    return jnp.where(mask, indices[safe_epos], FILL), safe_epos, mask

  return _draw_block(draw, seeds, seed_mask, k, key)


def edge_in_csr(indptr, indices, rows, cols):
  """Vectorized membership test: is (rows[i], cols[i]) an edge?

  Replacement for the reference's per-trial device binary search
  (csrc/cuda/random_negative_sampler.cu EdgeInCSR). Requires ``indices``
  sorted within each row segment (see ops.negative.sort_csr_segments).
  """
  start = indptr[rows]
  end = indptr[rows + 1]
  lo, hi = start, end

  def body(_, carry):
    lo, hi = carry
    mid = (lo + hi) // 2
    v = indices[jnp.clip(mid, 0, indices.shape[0] - 1)]
    go_right = v < cols
    lo = jnp.where(go_right, mid + 1, lo)
    hi = jnp.where(go_right, hi, mid)
    return lo, hi

  lo, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
  pos = jnp.clip(lo, 0, indices.shape[0] - 1)
  return (lo < end) & (indices[pos] == cols)
