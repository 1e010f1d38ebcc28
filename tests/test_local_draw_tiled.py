"""The shard-local draws take the received request block ``[P, cap]`` and
draw it tile by tile, only below its last valid column: the outputs equal
the one-piece draw's bit for bit (kept here as it was written, over the
block's ``P·cap`` slots in order) for uniform and weighted draws, one to
four buckets, blocks below and above the tile rule's threshold and masks
of every kind, and the loop runs ``ceil(last valid column / T)`` tiles.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.ops import neighbor, sorted_index
from test_dist_graph_index import ID_SPACE, _shard

K = 4                                   # under and over the degrees (0 - 8)
STATIC = ('k', 'shift', 'depth')


# ------------------------------------------- the one-piece form, as it was

def _one_piece_uniform(row_ids, indptr_loc, indices, seeds, seed_mask, k,
                       key, starts, shift, depth):
  import jax
  import jax.numpy as jnp
  seeds, seed_mask = seeds.reshape(-1), seed_mask.reshape(-1)
  b = seeds.shape[0]
  found, pos = neighbor._local_rows(row_ids, starts, seeds, seed_mask,
                                    shift, depth)
  start = indptr_loc[pos]
  deg = jnp.where(found, indptr_loc[pos + 1] - start, 0)
  u = jax.random.uniform(key, (b, k))
  rand_off = jnp.floor(u * deg[:, None].astype(u.dtype)).astype(jnp.int32)
  rand_off = jnp.minimum(rand_off, jnp.maximum(deg[:, None] - 1, 0))
  seq_off = jnp.arange(k, dtype=jnp.int32)[None, :]
  offsets = jnp.where(deg[:, None] > k, rand_off, seq_off)
  mask = found[:, None] & (offsets < deg[:, None])
  epos = start[:, None] + offsets
  safe_epos = jnp.where(mask, epos, 0)
  nbrs = jnp.where(mask, indices[safe_epos], glt.ops.FILL)
  return nbrs, jnp.where(mask, epos, 0), mask


def _one_piece_weighted(row_ids, indptr_loc, indices, row_cumsum, seeds,
                        seed_mask, k, key, starts, shift, depth):
  import jax
  import jax.numpy as jnp
  seeds, seed_mask = seeds.reshape(-1), seed_mask.reshape(-1)
  b = seeds.shape[0]
  found, pos = neighbor._local_rows(row_ids, starts, seeds, seed_mask,
                                    shift, depth)
  start = indptr_loc[pos]
  end = indptr_loc[pos + 1]
  deg = jnp.where(found, end - start, 0)
  end = start + deg
  total = row_cumsum[jnp.maximum(end - 1, 0)]
  total = jnp.where(deg > 0, total, 1.0)
  u = jax.random.uniform(key, (b, k)) * total[:, None]
  lo = jnp.broadcast_to(start[:, None], (b, k))
  hi = jnp.broadcast_to(end[:, None], (b, k))

  def body(_, carry):
    lo, hi = carry
    mid = (lo + hi) // 2
    go_right = row_cumsum[jnp.clip(mid, 0, row_cumsum.shape[0] - 1)] < u
    return jnp.where(go_right, mid + 1, lo), jnp.where(go_right, hi, mid)

  lo, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
  wpos = jnp.minimum(lo, jnp.maximum(end[:, None] - 1, 0))
  seq_off = jnp.arange(k, dtype=start.dtype)[None, :]
  epos = jnp.where(deg[:, None] > k, wpos, start[:, None] + seq_off)
  mask = found[:, None] & (
      jnp.where(deg[:, None] > k, 0, seq_off) < deg[:, None])
  safe_epos = jnp.where(mask, epos, 0)
  nbrs = jnp.where(mask, indices[safe_epos], glt.ops.FILL)
  return nbrs, jnp.where(mask, epos, 0), mask


# ---------------------------------------------------- the received blocks

FILLS = ('empty', 'one', 'third', 'two_thirds', 'one_full_row',
         'last_column', 'scattered', 'full')


def _block(own, p, cap, fill, seed=0):
  """A received block ``[p, cap]`` as the exchange packs it: every bucket
  a valid prefix (owned and unowned ids) followed by the pad -1 — or, for
  ``scattered`` and ``last_column``, valid slots that are no prefix."""
  rng = np.random.default_rng(seed)
  ids = np.where(rng.random((p, cap)) < 0.6,
                 rng.choice(own, (p, cap)) if own.shape[0] else 0,
                 rng.integers(0, ID_SPACE, (p, cap))).astype(np.int32)
  col = np.arange(cap)[None, :]
  if fill == 'scattered':
    valid = rng.random((p, cap)) < 0.5
  elif fill == 'last_column':
    valid = np.zeros((p, cap), bool)
    valid[0, cap - 1] = True
  else:
    lengths = {
        'empty': np.zeros(p, int), 'one': np.eye(1, p, dtype=int)[0],
        'third': rng.integers(0, cap // 3 + 1, p),
        'two_thirds': rng.integers(cap // 3, 2 * cap // 3 + 1, p),
        'one_full_row': np.eye(1, p, p - 1, dtype=int)[0] * cap,
        'full': np.full(p, cap)}[fill]
    valid = col < lengths[:, None]
  return np.where(valid, ids, -1).astype(np.int32)


def _last_valid_column(block):
  cols = np.nonzero((block >= 0).any(axis=0))[0]
  return int(cols[-1]) + 1 if cols.size else 0


@pytest.mark.parametrize('fill', FILLS)
@pytest.mark.parametrize('p, cap', [(1, 300), (4, 300), (1, 2560), (2, 2560),
                                    (4, 4100)],
                         ids=['1x300', '4x300', '1x2560', '2x2560',
                              '4x4100'])
@pytest.mark.parametrize('draw', ['uniform', 'weighted'])
def test_the_tiled_draw_is_the_one_piece_draw(draw, p, cap, fill):
  import jax
  import jax.numpy as jnp
  own, row_ids, indptr, indices, wcum = _shard('spread')
  ix = sorted_index.build_sorted_index_host(row_ids, ID_SPACE)
  block = jnp.asarray(_block(own, p, cap, fill, seed=p * cap))
  key = jax.random.PRNGKey(p * 1000 + cap)
  index = (ix.starts, ix.shift, ix.depth)
  if draw == 'uniform':
    got = glt.ops.uniform_sample_local(
        row_ids, indptr, indices, block, block >= 0, K, key, *index)
    want = jax.jit(_one_piece_uniform, static_argnames=STATIC)(
        row_ids, indptr, indices, block, block >= 0, K, key, *index)
  else:
    got = glt.ops.weighted_sample_local(
        row_ids, indptr, indices, wcum, block, block >= 0, K, key, *index)
    want = jax.jit(_one_piece_weighted, static_argnames=STATIC)(
        row_ids, indptr, indices, wcum, block, block >= 0, K, key, *index)
  for name, a, b in zip(('nbrs', 'epos', 'mask'), got, want):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == (p * cap, K) and a.dtype == b.dtype, name
    assert a.tobytes() == b.tobytes(), name
  hit = np.asarray(got[2]).any(axis=1)
  assert not hit[np.asarray(block).reshape(-1) < 0].any()
  assert hit.any() == (fill != 'empty' and bool(
      np.isin(np.asarray(block), own).any()))
  # the rule tiles the wide blocks and leaves the narrow ones whole
  assert bool(neighbor.draw_tile_rows(cap)) == (cap >= 2048)


def _echo(seeds, seed_mask, u):
  """A draw that reads nothing: each valid slot's id in every column."""
  import jax.numpy as jnp
  mask = seed_mask[:, None] & (u >= 0)
  return (jnp.where(mask, seeds[:, None], glt.ops.FILL),
          mask.astype(jnp.int32), mask)


@pytest.mark.parametrize('fill', FILLS)
@pytest.mark.parametrize('p, cap, tile', [(1, 2560, None), (4, 4100, None),
                                          (2, 300, 16), (4, 37, 8)],
                         ids=['1x2560', '4x4100', '2x300/16', '4x37/8'])
def test_the_loop_runs_the_tiles_below_the_last_valid_column(p, cap, tile,
                                                             fill):
  """``ceil(last valid column / T)`` tiles over all rows at once; slots of
  tiles not run read what a masked slot reads, the last tile is clamped
  to end at the cap, and a 1-D vector is a block of one row."""
  import jax
  import jax.numpy as jnp
  tile = tile or neighbor.draw_tile_rows(cap)
  block = _block(np.arange(ID_SPACE), p, cap, fill, seed=cap + p)
  u = jax.random.uniform(jax.random.PRNGKey(cap), (p * cap, 3))
  run = jax.jit(neighbor.draw_tiled, static_argnums=(0, 4))
  nbrs, ones, mask, tiles = run(_echo, jnp.asarray(block),
                                jnp.asarray(block >= 0), u, tile)
  assert tiles.dtype == jnp.int32
  assert int(tiles) == -(-_last_valid_column(block) // tile)
  want = np.where((block >= 0).reshape(-1)[:, None],
                  block.reshape(-1)[:, None], glt.ops.FILL)
  assert np.array_equal(np.asarray(nbrs), np.broadcast_to(want, (p * cap, 3)))
  assert np.array_equal(np.asarray(mask), np.asarray(nbrs) >= 0)
  assert np.array_equal(np.asarray(ones), np.asarray(mask).astype(np.int32))
  if p == 1:                             # the same over the flat vector
    flat = run(_echo, jnp.asarray(block[0]), jnp.asarray(block[0] >= 0), u,
               tile)
    for a, b in zip(flat, (nbrs, ones, mask, tiles)):
      assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_block_draw_lowers_to_one_loop_only_where_the_rule_tiles():
  import jax
  own, row_ids, indptr, indices, wcum = _shard('spread')
  ix = sorted_index.build_sorted_index_host(row_ids, ID_SPACE)
  key = jax.random.PRNGKey(0)

  def loops(cap):
    block = _block(own, 4, cap, 'third')
    text = glt.ops.uniform_sample_local.lower(
        row_ids, indptr, indices, block, block >= 0, K, key, ix.starts,
        ix.shift, ix.depth).as_text(debug_info=True)
    return text.count('stablehlo.while'), '/tile/' in text or '"tile/' in \
        text

  wide, narrow = loops(2560), loops(300)
  # the row lookup's halvings are a loop of their own in both
  assert wide[0] == narrow[0] + 1 and wide[1] and not narrow[1]
