"""Hotness reorder of feature rows by in-degree.

TPU-native port of /root/reference/graphlearn_torch/python/data/reorder.py:
rows are permuted so the hottest (highest in-degree) vertices come first,
which lets the feature store keep a prefix of rows in HBM and the tail on
host. Returns the permuted features plus the old-id -> new-row map
(``id2index``) that lookups must apply.
"""
from typing import Iterable, Tuple

import numpy as np


def in_degree_hotness(topology, num_nodes: int) -> np.ndarray:
  """[num_nodes] in-degree hotness scores (higher = hotter) — the
  ranking :func:`sort_by_in_degree` orders by, exposed standalone so the
  DISTRIBUTED feature store can select its replicated hot-cache set
  without reordering rows (DistFeature keeps ids canonical; only the
  local Feature relies on the hot-first permutation)."""
  in_deg = np.zeros((num_nodes,), dtype=np.int64)
  if topology.layout == 'CSC':
    d = topology.degrees
    in_deg[:d.shape[0]] = d
  else:
    np.add.at(in_deg, topology.indices,
              np.ones_like(topology.indices, dtype=np.int64))
  return in_deg


def frequency_hotness(id_batches: Iterable, num_nodes: int) -> np.ndarray:
  """[num_nodes] presampling frequency hotness: count how often each id
  appears across ``id_batches`` (arrays of visited node ids, e.g. the
  ``node`` buffers of a few warmup loader batches; negative FILL pads
  are ignored). Matches GLT's presampling hotness semantics — the ids a
  real workload touches, not a structural proxy."""
  counts = np.zeros((num_nodes,), dtype=np.int64)
  for ids in id_batches:
    ids = np.asarray(ids).reshape(-1)
    ids = ids[(ids >= 0) & (ids < num_nodes)]
    np.add.at(counts, ids, 1)
  return counts


def sort_by_in_degree(
    feature: np.ndarray,
    split_ratio: float,
    topology,
) -> Tuple[np.ndarray, np.ndarray]:
  """Reorder ``feature`` rows hot-first by in-degree.

  Reference semantics (reorder.py:19-36): only the hot prefix (fraction
  ``split_ratio``) needs to be degree-sorted; the reference partially
  shuffles within the split for load balance — here the full descending
  sort is kept (deterministic, and shard balance on TPU comes from XLA's
  row-sharding instead).

  Args:
    feature: [N, F] rows indexed by node id.
    split_ratio: fraction of rows that will live on device.
    topology: ``Topology`` whose in-degrees define hotness. If its layout is
      CSC, ``degrees`` are in-degrees already; if CSR, in-degrees are
      computed from the column indices.

  Returns:
    (reordered [N, F], id2index [N]) with reordered[id2index[v]] ==
    feature[v].
  """
  n = feature.shape[0]
  in_deg = in_degree_hotness(topology, n)
  del split_ratio  # full sort; ratio only matters to the caller's split
  order = np.argsort(-in_deg, kind='stable')  # hot first
  id2index = np.empty((n,), dtype=np.int64)
  id2index[order] = np.arange(n, dtype=np.int64)
  return feature[order], id2index


def hot_first_order(hotness: np.ndarray,
                    hot_rows: int) -> Tuple[np.ndarray, np.ndarray]:
  """``(index2id, id2index)``, both [N] int32, of the storage order that
  puts the ``hot_rows`` hottest ids first and leaves every other id in id
  order — without sorting N keys.

  The hot prefix is the one :func:`sort_by_in_degree`'s full stable sort
  gives (hotness descending, ties by ascending id), row for row; what
  follows it only has to be SOME fixed order, since nothing ranks rows
  that are not resident (the reference sorts the split's prefix too).
  The prefix's cut is found by a histogram over the hotness values, so
  the cost is O(N) and a sort of ``hot_rows`` keys: at 37 M rows a
  second, where the full argsort takes several and a device sort of N
  keys half a minute of the chip's compiler."""
  score = np.asarray(hotness).reshape(-1)
  n = score.shape[0]
  hot_rows = max(0, min(int(hot_rows), n))
  if score.size and score.min() < 0:
    raise ValueError('hotness scores are counts: none may be negative')
  hist = np.bincount(score)
  # above[t] = ids hotter than t; the cut is the hottest value that the
  # prefix cannot take whole
  above = hist[::-1].cumsum()[::-1] - hist
  cut = int(np.searchsorted(-above, -hot_rows, side='left'))
  cut = min(cut, hist.shape[0] - 1) if hist.size else 0
  hotter = np.flatnonzero(score > cut)
  tied = np.flatnonzero(score == cut)[:hot_rows - hotter.shape[0]]
  hot = np.concatenate([hotter, tied])
  hot = hot[np.lexsort((hot, -score[hot].astype(np.int64)))]
  is_hot = np.zeros((n,), bool)
  is_hot[hot] = True
  index2id = np.concatenate([hot, np.flatnonzero(~is_hot)]).astype(np.int32)
  id2index = np.empty((n,), np.int32)
  id2index[index2id] = np.arange(n, dtype=np.int32)
  return index2id, id2index
