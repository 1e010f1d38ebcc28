"""A rank directory over an ascending set of ids in a known id space.

A sorted id table answers "is ``q`` in it, and at which position" by
bisection: dependent reads, one a halving. Where the ids live in
``[0, N)`` the same answer takes one read of one table and one of a small
one. ``words[b]`` holds, for the block of ids ``16b … 16b + 15``, their 16
membership bits (the low half; unsigned, so every bit shifts and masks
alike) and the number of members from the start of the block's superblock
of 65,536 ids up to ``16b`` (the high half: at most 65,520, so it fits);
``rank[s]`` holds the number of members below superblock ``s``. ``q`` is a
member where bit ``q & 15`` of ``words[q >> 4]`` is set, and its position
in the ascending table is ``rank[q >> 16]`` plus that word's high half
plus the members below it in the block (a popcount). The answer is
:func:`~graphlearn_tpu.ops.unique.searchsorted_membership`'s over the
table for every int32 query, the position clamped into the table as there.

The bits and the ranks share one table so that a lookup program can keep
the whole directory in the chip's fast memory: ``N // 16 + 1`` words, 9.26
MB at 37 M ids, whatever the table's size (the last word covers id ``N``,
whose bit is never set), and ``N // 65536 + 1`` ranks. Split into a table
of bits and one of ranks a word, the v5e's compiler kept only one of the
two there and read the other from HBM at three times the price (PERF.md
section 6). The tables follow from the ids; nobody sets anything.
"""
import numpy as np

PER_SUPER = 4096            # words a superblock: 65,536 ids / 16


def directory_sizes(id_space: int):
  """Entries ``(words, rank)`` of the two tables over ``[0, id_space)``."""
  return (int(id_space) >> 4) + 1, (int(id_space) >> 16) + 1


def build_directory_host(ids, id_space: int):
  """``(words uint32 [W], rank int32 [S])`` of ascending distinct ids in
  ``[0, id_space)`` (a host array)."""
  w, _ = directory_sizes(id_space)
  bits = np.zeros(w * 16, bool)
  bits[np.asarray(ids, np.int64)] = True
  low = np.packbits(bits, bitorder='little').view('<u2').astype(np.uint32)
  counts = bits.reshape(w, 16).sum(axis=1)
  below = np.cumsum(counts) - counts
  rank = below[::PER_SUPER]
  high = below - rank[np.arange(w) // PER_SUPER]
  return low | (high.astype(np.uint32) << 16), rank.astype(np.int32)


def build_directory(ids, id_space: int):
  """:func:`build_directory_host` of a device array (traceable): one
  scatter of the bits — the ids are distinct, so adding them sets them —
  a popcount and a prefix sum."""
  import jax.numpy as jnp
  from jax import lax
  w, _ = directory_sizes(id_space)
  bits = jnp.left_shift(jnp.uint32(1), (ids & 15).astype(jnp.uint32))
  low = jnp.zeros((w,), jnp.uint32).at[ids >> 4].add(
      bits, indices_are_sorted=True)
  counts = lax.population_count(low).astype(jnp.int32)
  below = jnp.cumsum(counts, dtype=jnp.int32) - counts
  rank = below[::PER_SUPER]
  high = below - rank[jnp.arange(w) // PER_SUPER]
  return low | (high.astype(jnp.uint32) << 16), rank


def directory_membership(words, rank, queries, size: int):
  """Membership of ``queries`` in the ascending table of ``size`` ids the
  directory ``(words, rank)`` was built from: ``(found, pos)`` as
  :func:`searchsorted_membership` returns them over that table. A query
  outside the directory reads its first or its last word, clipped there
  (below 0: position 0; past the last word: ``size``, then clamped), and
  is no member."""
  import jax.numpy as jnp
  from jax import lax
  q = jnp.clip(queries, 0, 16 * words.shape[0] - 1)
  word = words[q >> 4]
  bit = (q & 15).astype(jnp.uint32)
  found = (((word >> bit) & 1) == 1) & (queries >= 0)
  lower = lax.population_count(word & ((jnp.uint32(1) << bit) - 1))
  pos = rank[q >> 16] + (word >> 16).astype(jnp.int32) + lower.astype(
      jnp.int32)
  return found, jnp.minimum(pos, size - 1)
