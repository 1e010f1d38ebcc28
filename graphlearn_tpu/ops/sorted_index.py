"""A two-level index over a sorted id table.

``jnp.searchsorted`` over an ascending id table of ``n`` rows is
``log2(n)`` dependent gathers a query (24 at 9 M rows), each a trip to
HBM. The ids live in a known id space ``[0, N)``, so the table can be cut
into buckets of ``2^shift`` consecutive IDS: ``starts[j]`` is the position
of the first entry ``>= j << shift``. A lookup reads the two starts around
its query and bisects between them only — ``depth`` halvings, a static
count fixed when the index is built from the largest bucket found (never
more than ``shift + 1`` for distinct ids). The answer is
:func:`~graphlearn_tpu.ops.unique.searchsorted_membership`'s for every
int32 query, padding and out-of-range ones included.

``shift`` follows from the shapes (about eight entries a bucket on
average), ``depth`` from the table; nobody sets either.
"""
import math
from typing import NamedTuple

import numpy as np


class SortedIndex(NamedTuple):
  """A built index: ``starts`` ``[..., (N >> shift) + 2]`` int32 (a host
  or a device array; leading axes are the table's), and the two statics
  a lookup is traced with."""
  starts: object
  shift: int
  depth: int


def index_shift(rows: int, id_space: int) -> int:
  """Bucket width (as a shift) for a table of ``rows`` entries over
  ``[0, id_space)``: ``log2(N / rows)`` to the nearest whole, plus 3 —
  about eight entries a bucket where the ids are spread evenly."""
  ratio = max(int(id_space), 1) / max(int(rows), 1)
  return max(0, math.floor(math.log2(ratio) + 0.5)) + 3


def bucket_bounds(id_space: int, shift: int) -> np.ndarray:
  """The ``(N >> shift) + 2`` lower id bounds the starts are searched
  for; the last lies past every id of the space."""
  return (np.arange((id_space >> shift) + 2, dtype=np.int64)
          << shift).astype(np.int32)


def index_depth(max_bucket: int) -> int:
  """Halvings that resolve a bucket of ``max_bucket`` entries."""
  return int(max_bucket).bit_length()


def bucket_starts(table, id_space: int, shift: int):
  """``starts`` of ONE ascending ``[n]`` table on the device it lives on
  (traceable: the store wraps it in its mesh's ``shard_map``), and the
  largest bucket's size as a device scalar."""
  import jax.numpy as jnp
  starts = jnp.searchsorted(
      table, jnp.asarray(bucket_bounds(id_space, shift))).astype(jnp.int32)
  return starts, jnp.max(starts[1:] - starts[:-1])


def index_shards_fn(mesh, id_space: int, shift: int):
  """The program ``tables [P, n] -> (starts [P, S], largest bucket)``:
  every shard's index built on the device its table lives on, one
  ``shift`` for all (the shards run one lookup program), the largest
  bucket of any shard replicated for the host to size ``depth`` from."""
  import jax
  from jax.sharding import PartitionSpec as P

  from ..utils.compat import shard_map
  ax = tuple(mesh.axis_names)

  def body(table):
    starts, big = bucket_starts(table[0], id_space, shift)
    return starts[None], jax.lax.pmax(big, ax)

  return jax.jit(shard_map(body, mesh=mesh, in_specs=P(ax),
                           out_specs=(P(ax), P()),
                           check_replication=False))


def build_sorted_index_shards(mesh, tables, id_space: int) -> SortedIndex:
  """The index of ``[P, n]`` tables sharded on their leading axis over
  ``mesh``, built where they live: one program, and the largest bucket
  the one scalar fetched."""
  import jax
  shift = index_shift(tables.shape[-1], id_space)
  starts, big = index_shards_fn(mesh, id_space, shift)(tables)
  return SortedIndex(starts, shift, index_depth(jax.device_get(big)))


def build_sorted_index_host(table: np.ndarray, id_space: int) -> SortedIndex:
  """The index of a host table ``[n]``, or of ``[P, n]`` tables that
  share one ``shift`` and one ``depth`` (a store's shards run one
  program)."""
  table = np.asarray(table)
  shift = index_shift(table.shape[-1], id_space)
  bounds = bucket_bounds(id_space, shift)
  flat = table.reshape(-1, table.shape[-1])
  starts = np.stack([np.searchsorted(t, bounds) for t in flat]).astype(
      np.int32)
  depth = index_depth((starts[:, 1:] - starts[:, :-1]).max())
  return SortedIndex(starts.reshape(table.shape[:-1] + bounds.shape),
                     shift, depth)


def indexed_membership(table, starts, queries, shift: int, depth: int):
  """Membership of ``queries`` in the ascending ``table`` (int-max
  padding at the tail allowed) through its index: ``(found, pos)`` as
  :func:`searchsorted_membership` returns them, ``pos`` clamped into the
  table."""
  import jax.numpy as jnp
  from jax import lax
  last = starts.shape[0] - 1
  j = jnp.clip(queries >> shift, 0, last)
  lo = starts[j]
  hi = starts[jnp.minimum(j + 1, last)]
  top = table.shape[0] - 1

  def halve(_, bounds):
    lo, hi = bounds
    mid = (lo + hi) >> 1
    # lo == hi is settled: a step there must not move it
    right = (lo < hi) & (table[jnp.minimum(mid, top)] < queries)
    return jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)

  lo, _ = lax.fori_loop(0, depth, halve, (lo, hi))
  pos = jnp.minimum(lo, top)
  return table[pos] == queries, pos
