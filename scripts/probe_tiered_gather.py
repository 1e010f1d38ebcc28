"""One-chip probe of the tiered gather's slab search (storage/scan.py
``tiered_gather``) at the tiered cell's shapes, bounded against one piece,
by the hot prefix's hit share: ``chiprun --chips 1 -- python3
scripts/probe_tiered_gather.py``.

The tiered cell reads the search at ONE mix (85 % of the lookups hits, a
third of the slots pads). This builds a node buffer of 432,384 slots in the
cell's three hop segments (a valid prefix, then pads), a 524,288-row slab
93 % full and a 5.55 M-row hot prefix, and times ``tiered_gather`` as it
stands, the one-piece form it replaced, and the lookup cut short after each
of its parts (remap, rank, compaction, tile loop and way back) and two forms
not taken (``lookup_alt``), for hit shares from 100 % down to 0. It checks that the two forms return the same bytes
and prints one ``micro:`` JSON line a case (program times off the device
trace, ``utils.device_program_ms``, mean of ``REPS`` calls). PERF.md
section 6, PR 42 has the readings.
"""
import json, os, re, sys, tempfile
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import graphlearn_tpu as glt
from graphlearn_tpu.ops.neighbor import draw_tile_rows
from graphlearn_tpu.ops.unique import searchsorted_membership
from graphlearn_tpu.storage import scan

N, HOT, F = 37_019_985, 5_552_998, 128
SEGMENTS, VALID = (16_384, 112_512, 303_488), 0.668   # 432,384 slots
SLAB, SLAB_FILL = 524_288, 490_000
REPS = 5
if '--tiny' in sys.argv:          # the CPU rehearsal: no time is read
  N, HOT, F, SEGMENTS, SLAB, SLAB_FILL = (
      200_000, 30_000, 8, (512, 1_024, 2_560), 4_096, 3_500)
INT32_MAX = np.iinfo(np.int32).max
rng = np.random.default_rng(42)


def one_piece(hot, slab_ids, slab, id2i, node):
  """``tiered_gather`` before PR 42: every slot searched."""
  h = hot.shape[0]
  safe = jnp.maximum(node, 0)
  ridx = id2i[safe].astype(jnp.int32)
  pos = jnp.clip(jnp.searchsorted(slab_ids, ridx), 0, slab_ids.shape[0] - 1)
  in_slab = slab_ids[pos] == ridx
  hot_rows = hot[jnp.clip(ridx, 0, h - 1)]
  return jnp.where((ridx < h)[:, None], hot_rows,
                   jnp.where(in_slab[:, None], slab[pos], 0))


def lookup_upto(part):
  """The bounded lookup cut after ``part``; returns what that part made
  (so nothing before it is dead code)."""
  def fn(slab_ids, id2i, node):
    cap = node.shape[0]
    tile = draw_tile_rows(cap)
    ridx = id2i[jnp.maximum(node, 0)].astype(jnp.int32)
    if part == 'remap':
      return ridx
    miss = (node >= 0) & (ridx >= HOT)
    rank = jnp.cumsum(miss, dtype=jnp.int32) - 1
    if part == 'rank':
      return rank
    queries = jnp.full((cap,), INT32_MAX, jnp.int32).at[
        jnp.where(miss, rank, cap)].set(ridx, mode='drop')
    if part == 'compact':
      return queries, rank
    assert part == 'whole', part
    return scan.bounded_slab_search(slab_ids, ridx, miss, tile)
  fn.__name__ = f'lookup_{part}'
  return fn


def lookup_alt(kind):
  """Two forms the library does not take, timed beside it: ``unique`` —
  the compaction scatter told its indices are unique (a dropped slot
  aims past the buffer at an address of its own); ``pair`` — the way back
  as a scatter: the compaction carries each query's slot beside its row
  (one scatter of 8-byte pairs) and a tile scatters its answers home."""
  def fn(slab_ids, id2i, node):
    cap = node.shape[0]
    tile = draw_tile_rows(cap)
    ridx = id2i[jnp.maximum(node, 0)].astype(jnp.int32)
    miss = (node >= 0) & (ridx >= HOT)
    rank = jnp.cumsum(miss, dtype=jnp.int32) - 1
    slot = jnp.arange(cap, dtype=jnp.int32)
    if kind == 'unique':
      return jnp.full((cap,), INT32_MAX, jnp.int32).at[
          jnp.where(miss, rank, cap + slot)].set(
              ridx, mode='drop', unique_indices=True), rank
    pairs = jnp.full((cap, 2), INT32_MAX, jnp.int32).at[
        jnp.where(miss, rank, cap)].set(
            jnp.stack([ridx, slot], 1), mode='drop')

    def body(i, code):
      lo = jnp.minimum(i * tile, cap - tile)
      q = jax.lax.dynamic_slice(pairs, (lo, 0), (tile, 2))
      found, pos = searchsorted_membership(slab_ids, q[:, 0])
      return code.at[q[:, 1]].set(jnp.where(found, pos, -1), mode='drop')

    return jax.lax.fori_loop(0, (rank[-1] + tile) // tile, body,
                             jnp.full((cap,), -1, jnp.int32))
  fn.__name__ = f'lookup_alt_{kind}'
  return fn


def case(hit, id2i_np, inv):
  """A node buffer whose valid slots hit the hot prefix with share
  ``hit``, and the slab that holds its misses."""
  node = np.full((sum(SEGMENTS),), -1, np.int32)
  at = 0
  for seg in SEGMENTS:
    n = int(seg * VALID)
    cold = rng.random(n) >= hit
    rows = np.where(cold, rng.integers(HOT, N, n), rng.integers(0, HOT, n))
    node[at:at + n] = inv[rows]
    at += seg
  ridx = id2i_np[np.maximum(node, 0)]
  mine = np.unique(ridx[ridx >= HOT])
  assert mine.shape[0] <= SLAB, mine.shape
  ids = np.unique(np.concatenate([mine, rng.integers(
      HOT, N, max(SLAB_FILL - mine.shape[0], 0))]))
  slab_ids = np.full((SLAB,), INT32_MAX, np.int32)
  slab_ids[:ids.shape[0]] = ids
  misses = int(((node >= 0) & (ridx >= HOT)).sum())
  return node, slab_ids, dict(
      hit_share=hit, slots=int(node.shape[0]), valid=int((node >= 0).sum()),
      misses=misses, tile=draw_tile_rows(node.shape[0]),
      tiles=-(-misses // draw_tile_rows(node.shape[0])))


def main():
  perm = rng.permutation(N).astype(np.int32)        # node id -> storage row
  inv = np.empty_like(perm)
  inv[perm] = np.arange(N, dtype=np.int32)
  id2i = jnp.asarray(perm)
  hot = jax.jit(lambda: (jnp.arange(HOT, dtype=jnp.float32)[:, None]
                         + jnp.arange(F, dtype=jnp.float32)[None]))()
  slab = jax.jit(lambda: (jnp.arange(SLAB, dtype=jnp.float32)[:, None]
                          - jnp.arange(F, dtype=jnp.float32)[None]))()

  def bounded(hot, slab_ids, slab, id2i, node):
    return scan.tiered_gather(hot, slab_ids, slab, id2i, node)

  whole = {f.__name__: jax.jit(f) for f in (bounded, one_piece)}
  parts = {f.__name__: jax.jit(f) for f in (
      *map(lookup_upto, ('remap', 'rank', 'compact', 'whole')),
      *map(lookup_alt, ('unique', 'pair')))}
  for hit in (0.8525, 1.0, 0.6, 0.4, 0.0):
    node_np, slab_ids_np, res = case(hit, perm, inv)
    node, slab_ids = jnp.asarray(node_np), jnp.asarray(slab_ids_np)
    outs = {k: np.asarray(f(hot, slab_ids, slab, id2i, node))
            for k, f in whole.items()}
    res['equal'] = outs['bounded'].tobytes() == outs['one_piece'].tobytes()
    del outs
    for f in parts.values():
      jax.block_until_ready(f(slab_ids, id2i, node))
    code, tiles = parts['lookup_whole'](slab_ids, id2i, node)
    res['tiles_run'] = int(tiles)
    res['alt_equal'] = bool(
        (parts['lookup_alt_pair'](slab_ids, id2i, node) == code).all()
        and (parts['lookup_alt_unique'](slab_ids, id2i, node)[0]
             == parts['lookup_compact'](slab_ids, id2i, node)[0]).all())
    with tempfile.TemporaryDirectory() as d:
      with glt.utils.profile_trace(d):
        for _ in range(REPS):
          for f in whole.values():
            jax.block_until_ready(f(hot, slab_ids, slab, id2i, node))
          for f in parts.values():
            jax.block_until_ready(f(slab_ids, id2i, node))
      ms = glt.utils.device_program_ms(d)
    for name in list(whole) + list(parts):
      got = [v for n, v in ms.items() if re.match(f'jit_{name}(\\D|$)', n)]
      assert len(got) <= 1 and all(c == REPS for _, c in got), ms
      res[name + '_ms'] = round(got[0][0], 4) if got else None
    print('micro: ' + json.dumps(res), flush=True)


if __name__ == '__main__':
  main()
