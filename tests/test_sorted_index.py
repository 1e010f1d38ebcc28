"""The two-level index over a sorted id table (ops/sorted_index.py).

One contract: ``indexed_membership`` returns
``searchsorted_membership``'s ``(found, pos)`` exactly, for every int32
query, over every kind of table the mesh feature store keeps — and the
index a device builds is the index the host builds.
"""
import numpy as np
import pytest

from graphlearn_tpu.ops import searchsorted_membership
from graphlearn_tpu.ops import sorted_index as si

MAX = np.iinfo(np.int32).max


def _owned(n, parts, p, rule, seed=0):
  """Partition ``p``'s sorted ids of ``[0, n)`` under a partition rule."""
  if rule == 'mod':
    return np.arange(p, n, parts, dtype=np.int32)
  book = np.random.default_rng(seed).integers(0, parts, n)
  return np.nonzero(book == p)[0].astype(np.int32)


def _pad(ids, n_max):
  return np.concatenate([ids, np.full(n_max - ids.shape[0], MAX, np.int32)])


def _clustered(n):
  """A sparse table (shift > 3) with one bucket full to its ``2^shift``
  ids, long empty stretches, and a last id in the space's ragged tail."""
  shift = si.index_shift(n // 16, n)
  full = np.arange(5 << shift, 6 << shift)
  rest = np.arange(0, n, 16)
  return np.unique(np.concatenate([full, rest[: n // 16 - full.shape[0]],
                                   [n - 1]])).astype(np.int32)


CASES = {
    # name: (table, id space)
    'dense_every_id': (np.arange(1000, dtype=np.int32), 1000),
    'sparse_random': (np.sort(np.random.default_rng(1).choice(
        5000, 130, replace=False)).astype(np.int32), 5000),
    'mod_book_shard': (_owned(1003, 4, 1, 'mod'), 1003),
    'mod_book_shard_padded': (_pad(_owned(1003, 4, 3, 'mod'), 251), 1003),
    'random_book_shard_padded': (_pad(_owned(1003, 4, 2, 'random'), 300),
                                 1003),
    'empty_buckets_and_a_full_one': (_clustered(4099), 4099),
    'space_not_a_multiple_of_the_bucket': (
        np.arange(3, 1001, 7, dtype=np.int32), 1001),
    'one_entry_pad_table': (np.full((1,), MAX, np.int32), 40),
    'one_entry_pad_table_wide_space': (np.full((1,), MAX, np.int32),
                                       37_019_985),
    'all_padding': (np.full((9,), MAX, np.int32), 64),
    'duplicate_ids': (np.sort(np.random.default_rng(2).integers(
        0, 300, 200)).astype(np.int32), 300),
    'tiny_store_forty_ids': (_pad(_owned(40, 4, 0, 'random', 3), 14), 40),
}


def _queries(n, shift):
  """Every id of the space and a margin on both sides, then the values
  the programs pad with: FILL, INT32_MAX, the ends of int32."""
  lo, hi = -5, min(n + (2 << shift) + 5, 70_000)
  q = np.concatenate([
      np.arange(lo, hi, dtype=np.int64),
      [n - 1, n, n + 1, -1, MAX, MAX - 1, np.iinfo(np.int32).min,
       1 << 30]])
  return q.astype(np.int32)


@pytest.mark.parametrize('name', sorted(CASES))
def test_indexed_membership_is_searchsorted_membership(name):
  import jax
  import jax.numpy as jnp
  table, n = CASES[name]
  idx = si.build_sorted_index_host(table, n)
  assert idx.starts.shape == ((n >> idx.shift) + 2,)
  sizes = np.diff(idx.starts)
  assert idx.depth == int(sizes.max()).bit_length()
  if np.unique(table).shape[0] == table.shape[0]:
    assert sizes.max() <= 1 << idx.shift    # distinct ids: a bucket's ids
    assert idx.depth <= idx.shift + 1
  q = _queries(n, idx.shift)
  want = searchsorted_membership(jnp.asarray(table), jnp.asarray(q))
  got = jax.jit(si.indexed_membership, static_argnums=(3, 4))(
      jnp.asarray(table), jnp.asarray(idx.starts), jnp.asarray(q),
      idx.shift, idx.depth)
  for w, g, what in zip(want, got, ('found', 'pos')):
    assert np.array_equal(np.asarray(w), np.asarray(g)), (name, what)
  # padding and out-of-range queries are never found in the real ids
  real = table[table != MAX]
  found = np.asarray(got[0])
  assert not found[(q < 0) | ((q >= n) & (q != MAX))].any()
  assert np.array_equal(found[(q >= 0) & (q < n)],
                        np.isin(q[(q >= 0) & (q < n)], real))
  # the device builds the host's index
  starts, big = jax.jit(si.bucket_starts, static_argnums=(1, 2))(
      jnp.asarray(table), n, idx.shift)
  assert np.asarray(starts).dtype == np.int32
  assert np.array_equal(np.asarray(starts), idx.starts)
  assert si.index_depth(int(big)) == idx.depth


def test_the_full_bucket_case_is_full():
  """The clustered fixture really holds a bucket at its maximum, empty
  buckets, and runs ``shift + 1`` halvings."""
  table, n = CASES['empty_buckets_and_a_full_one']
  idx = si.build_sorted_index_host(table, n)
  sizes = np.diff(idx.starts)
  assert sizes.max() == 1 << idx.shift and (sizes == 0).any()
  assert idx.depth == idx.shift + 1 and idx.shift > 3


@pytest.mark.parametrize('rows,space,shift', [
    (9_254_997, 37_019_985, 5),     # the mesh cell's row shards
    (1_850_999, 37_019_985, 7),     # its hot cache
    (1, 40, 8), (1000, 1000, 3), (10, 40, 5), (5000, 1000, 3), (7, 0, 3)])
def test_shift_follows_from_the_shapes(rows, space, shift):
  assert si.index_shift(rows, space) == shift
  bounds = si.bucket_bounds(space, shift)
  assert bounds.shape == ((space >> shift) + 2,) and bounds[0] == 0
  assert bounds[-1] > max(space - 1, 0) and bounds.dtype == np.int32


def test_shards_of_a_store_share_shift_and_depth():
  """``[P, n]`` tables: one shift, the depth of the fullest bucket of any
  shard, starts per shard."""
  n, parts = 1003, 4
  shards = [_owned(n, parts, p, 'random', 7) for p in range(parts)]
  n_max = max(s.shape[0] for s in shards)
  table = np.stack([_pad(s, n_max) for s in shards])
  idx = si.build_sorted_index_host(table, n)
  assert idx.starts.shape == (parts, (n >> idx.shift) + 2)
  depths = []
  for p in range(parts):
    one = si.build_sorted_index_host(table[p], n)
    assert one.shift == idx.shift
    assert np.array_equal(one.starts, idx.starts[p])
    depths.append(one.depth)
  assert idx.depth == max(depths)
