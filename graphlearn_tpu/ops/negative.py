"""Fixed-shape random negative edge sampling.

TPU-native replacement for the reference negative samplers
(/root/reference/graphlearn_torch/csrc/cuda/random_negative_sampler.cu and
csrc/cpu/random_negative_sampler.cc): draw candidate (row, col) pairs, reject
pairs present in the CSR via binary search, and keep the first ``num_samples``
survivors. The CUDA version loops trials with thrust compaction and a D2H
count; here all ``trials * num_samples`` candidates are drawn and tested in
one fixed-shape pass, and compaction is an argsort — no host sync.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from .neighbor import edge_in_csr


def sort_csr_segments(indptr: np.ndarray, indices: np.ndarray):
  """Host-side: sort ``indices`` within each row segment (binary-search
  membership requires sorted rows). Returns (sorted_indices, perm) where
  ``perm`` maps sorted edge positions back to original CSR positions."""
  indptr = np.asarray(indptr)
  indices = np.asarray(indices)
  rows = np.repeat(np.arange(indptr.shape[0] - 1),
                   np.diff(indptr))
  perm = np.lexsort((indices, rows))
  return indices[perm], perm


#: edges a window of :func:`sort_csr_segments_device` holds (a power of
#: two; a graph whose longest row is longer gets the next one above it)
SEGMENT_SORT_WINDOW = 1 << 22


def sort_csr_segments_device(indptr, indices, window: int = None):
  """``sort_csr_segments``' sorted indices, made where the CSR lives: a
  device array in, a NEW device array out, nothing of size E on the host.

  The edge list is walked in windows of ``window`` edges that start and
  end on row boundaries (``indptr`` is read on the host: N + 1 numbers).
  One program, compiled once for the graph, sorts a window by (row,
  neighbour) and writes it back in place: slots of the window outside
  its rows keep their position (their key is their own index), so the
  fixed-size write never disturbs a neighbouring window."""
  ptr = np.asarray(indptr).astype(np.int64)
  num_edges = int(ptr[-1])
  out = jnp.array(indices, copy=True)
  if num_edges == 0:
    return out
  width = window or SEGMENT_SORT_WINDOW
  longest = int(np.diff(ptr).max())
  while width < longest:
    width *= 2
  width = min(width, num_edges)
  bounds = [0]
  while bounds[-1] < num_edges:
    # the last row boundary at most `width` edges on (a row is never cut)
    stop = int(ptr[np.searchsorted(ptr, bounds[-1] + width, 'right') - 1])
    bounds.append(stop)
  ptr_dev = jnp.asarray(ptr.astype(np.int32))
  for lo, hi in zip(bounds[:-1], bounds[1:]):
    out = _sort_window(out, ptr_dev, np.int32(lo), np.int32(hi),
                       width=width)
  return out


@functools.partial(jax.jit, static_argnames=('width',), donate_argnums=(0,))
def _sort_window(indices, indptr, lo, hi, width: int):
  start = jnp.minimum(lo, indices.shape[0] - width)
  pos = start + jnp.arange(width, dtype=jnp.int32)
  vals = jax.lax.dynamic_slice(indices, (start,), (width,))
  inside = (pos >= lo) & (pos < hi)
  rows = jnp.searchsorted(indptr, pos, side='right',
                          method='sort').astype(jnp.int32) - 1
  big = jnp.iinfo(jnp.int32).max
  k1 = jnp.where(inside, rows, jnp.where(pos < lo, -1, big))
  k2 = jnp.where(inside, vals, pos)
  _, _, vals = jax.lax.sort((k1, k2, vals), num_keys=2)
  return jax.lax.dynamic_update_slice(indices, vals, (start,))


@functools.partial(jax.jit,
                   static_argnames=('num_samples', 'trials', 'padding',
                                    'with_counts'))
def random_negative_sample(indptr, sorted_indices, num_src, num_dst,
                           num_samples: int, key, trials: int = 5,
                           padding: bool = False,
                           with_counts: bool = False):
  """Sample (row, col) pairs absent from the CSR.

  Args:
    indptr/sorted_indices: CSR with row-sorted indices
      (:func:`sort_csr_segments`).
    num_src/num_dst: id ranges for rows/cols.
    num_samples: number of pairs wanted (static).
    trials: candidate multiplier; ``trials * num_samples`` candidates are
      tested (reference semantics: retry up to ``trials_num`` rounds,
      random_negative_sampler.cu).
    padding: non-strict mode — pad any shortfall with random (possibly
      positive) pairs so the output is always full (reference ``padding``
      flag).

  Returns (rows [num_samples], cols [num_samples], mask [num_samples]);
  with ``with_counts`` also an int32 ``[3]``: candidates tested, those
  rejected as edges, and output slots no non-edge was left for (which
  ``padding`` fills with rejected candidates, in draw order).
  """
  total = num_samples * trials
  kr, kc = jax.random.split(key)
  rows = jax.random.randint(kr, (total,), 0, num_src, dtype=jnp.int32)
  cols = jax.random.randint(kc, (total,), 0, num_dst, dtype=jnp.int32)
  is_edge = edge_in_csr(indptr, sorted_indices, rows, cols)
  valid = ~is_edge
  # Stable partition: valid candidates first, in draw order.
  order = jnp.argsort(jnp.where(valid, 0, 1), stable=True)
  take = order[:num_samples]
  out_rows = rows[take]
  out_cols = cols[take]
  out_mask = valid[take]
  counts = jnp.stack([jnp.int32(total), is_edge.sum(dtype=jnp.int32),
                      (~out_mask).sum(dtype=jnp.int32)])
  if padding:
    out_mask = jnp.ones_like(out_mask)
  if with_counts:
    return out_rows, out_cols, out_mask, counts
  return out_rows, out_cols, out_mask


def random_negative_sample_local(row_ids, indptr_loc, sorted_indices,
                                 num_dst: int, num_samples: int, key,
                                 trials: int = 5, strict: bool = False):
  """Shard-local negative sampling for the distributed engine.

  Each shard draws source rows from ITS OWN partition's local CSR.
  Candidate (local_row, dst) pairs are rejected when present in the
  local CSR segment; survivors map to global ids via ``row_ids``.

  STRICTNESS: the engine's partition invariant is that a row's COMPLETE
  out-edge set lives on its owner's shard (the exchange samples node v
  only on owner(v) — splitting a row across shards would undersample),
  so the local membership check is globally complete for locally-drawn
  sources. ``strict=False`` (reference parity: its distributed path
  cannot check remote edges at all, dist_neighbor_sampler.py:380-383)
  always emits ``num_samples`` pairs, letting a candidate that stayed
  an edge through every trial slip through. ``strict=True`` marks such
  slots invalid instead — every VALID pair is guaranteed a non-edge,
  beyond the reference's distributed contract.

  Traced inside shard_map (no jit wrapper; the caller's program compiles
  it). Returns (src_global [num_samples], dst [num_samples],
  valid [num_samples]) — ``valid`` is all-False on a shard that owns zero
  rows of this CSR (skewed partitioning of a rare edge type), so callers
  must mask those slots out of the seed union instead of treating the
  INT_MAX row padding as node ids.
  """
  num_actual = jnp.sum(row_ids != jnp.iinfo(row_ids.dtype).max
                       ).astype(jnp.int32)
  num_rows = jnp.maximum(num_actual, 1)
  total = num_samples * trials
  kr, kc = jax.random.split(key)
  u = jax.random.randint(kr, (total,), 0, jnp.int32(2 ** 30),
                         dtype=jnp.int32) % num_rows
  cols = jax.random.randint(kc, (total,), 0, num_dst, dtype=jnp.int32)
  is_edge = edge_in_csr(indptr_loc, sorted_indices, u, cols)
  order = jnp.argsort(jnp.where(is_edge, 1, 0), stable=True)
  take = order[:num_samples]
  valid = jnp.broadcast_to(num_actual > 0, (num_samples,))
  if strict:
    valid = valid & ~is_edge[take]
  src = jnp.where(valid, row_ids[u[take]].astype(jnp.int32), -1)
  return src, jnp.where(valid, cols[take], -1), valid
