"""Embedding stores the online endpoint answers from.

Two backings behind one ``lookup(ids, mask) -> rows`` surface:

* :class:`EmbeddingStore` — a single-replica device-resident
  ``[N, F]`` table with one persistent jitted gather per padded bucket
  capacity, plus a donated scatter for the final-layer refresh
  write-back.
* :class:`DistEmbeddingStore` — the sharded variant: the materialized
  table is row-partitioned into a ``DistFeature`` over the serving
  mesh, REUSING the feature path's hot-vertex split/hotness machinery
  wholesale (distributed/dist_feature.py): the globally hottest
  embedding rows are replicated per shard (the hot-EMBEDDING cache —
  DCI's workload-aware cache, arxiv 2503.01281, in GLT terms), misses
  dedup into the bucketed miss-only exchange, and the ``[P, 4]``
  hit/miss stats ride on device until ``publish_stats``.

Both stores keep every lookup ONE program dispatch over a closed set of
static shapes (GNNSampler, arxiv 2108.11571): the engine pads request
batches to calibrated bucket capacities, so each capacity compiles once
and serves all traffic.
"""
from typing import Optional

import numpy as np

from ..utils.trace import record_dispatch


def pow2_cap(n: int, floor: int = 8) -> int:
  """The padded power-of-two bucket capacity for ``n`` items — ONE
  formula shared by the refresh compute buckets
  (EmbeddingMaterializer.refresh_rows) and the write-back scatter
  buckets (EmbeddingStore.update_rows), so the two closed program sets
  stay in lockstep."""
  return max(floor, 1 << int(n - 1).bit_length()) if n > 1 else floor


class EmbeddingStore:
  """Single-replica device-resident embedding table.

  ``embeddings``: [N_pad, F] rows. ``num_nodes``: the REAL node count —
  REQUIRED knowledge for materializer tables, whose rows past
  ``num_nodes`` are block padding: defaulting to the table height would
  let the engine's id validation serve pad rows as real nodes. Prefer
  ``EmbeddingMaterializer.embedding_store()``, which passes it for you.
  ``granularity`` is the bucket divisibility the engine must respect
  (1: any capacity compiles).

  The store TAKES OWNERSHIP of the table: :meth:`update_rows` donates
  the buffer (the table is replaced in place, HBM stays flat), so after
  the first refresh write-back the array handed in here is dead — read
  embeddings through the store, not through a kept reference.
  """

  granularity = 1

  #: route the bucket gather through the run-segmented DMA kernel
  #: (ops.gather_rows_hbm2; set before the first lookup) — the same gate
  #: as UnifiedTensor: inert off-TPU; on TPU a width the kernel cannot
  #: serve raises
  use_pallas_v2 = False

  def __init__(self, embeddings, num_nodes: Optional[int] = None):
    import jax
    self._emb = jax.device_put(np.asarray(embeddings)) \
        if isinstance(embeddings, np.ndarray) else embeddings
    self.num_nodes = int(num_nodes if num_nodes is not None
                         else self._emb.shape[0])
    # ONE jitted gather/scatter each: jax.jit's own cache already
    # specializes per capacity, so the program set stays exactly
    # one-executable-per-bucket without per-cap bookkeeping here
    self._gather = None
    self._scatter = None

  @property
  def feature_dim(self) -> int:
    return int(self._emb.shape[1])

  def _gather_fn(self):
    if self._gather is None:
      import jax
      import jax.numpy as jnp
      from ..ops.gather_pallas import (_gather_rows_hbm2_impl,
                                       table_kernel_ok)
      if self.use_pallas_v2 and table_kernel_ok('EmbeddingStore',
                                                self._emb):

        def gather(emb, ids, mask):
          rows = _gather_rows_hbm2_impl(
              emb, jnp.maximum(ids, 0).astype(jnp.int32))
          return jnp.where(mask[:, None], rows, 0)
      else:

        def gather(emb, ids, mask):
          rows = emb[jnp.maximum(ids, 0)]
          return jnp.where(mask[:, None], rows, 0)

      from ..metrics import programs
      self._gather = programs.instrument(jax.jit(gather),
                                         'serve_lookup')
    return self._gather

  def lookup(self, ids, mask):
    """[cap] padded ids (-1 pads, mask False) -> [cap, F] device rows.
    One dispatch; the capacity's program persists across requests."""
    import jax
    import jax.numpy as jnp
    ids = jnp.asarray(ids)
    fn = self._gather_fn()
    if self.use_pallas_v2 and jax.default_backend() == 'tpu':
      # the program above routed the kernel (it raised otherwise)
      from .. import metrics
      metrics.inc('ops.gather_runs')
    record_dispatch('serve_lookup')
    return fn(self._emb, ids, jnp.asarray(mask))

  def fetch(self, rows) -> np.ndarray:
    """Device rows -> host (the engine's single fetch per batch)."""
    return np.asarray(rows)

  def update_rows(self, ids, rows):
    """Refresh write-back: scatter ``rows`` into the table at ``ids``
    (donated update — the table is replaced, not copied). Padded to
    power-of-two capacities like the refresh compute, so the write-back
    program set stays CLOSED under varying stale counts (pad slots
    scatter out of bounds and are dropped)."""
    import jax
    import jax.numpy as jnp
    ids = np.asarray(ids, np.int64).reshape(-1)
    if ids.size == 0:
      return
    rows = np.asarray(rows)
    cap = pow2_cap(ids.size)
    n_pad = int(self._emb.shape[0])
    idx = np.full((cap,), n_pad, np.int64)     # OOB: dropped by 'drop'
    idx[:ids.size] = ids
    vals = np.zeros((cap, rows.shape[1]), rows.dtype)
    vals[:ids.size] = rows
    if self._scatter is None:

      def scatter(emb, idx, vals):
        return emb.at[idx].set(vals.astype(emb.dtype), mode='drop')

      from ..metrics import programs
      self._scatter = programs.instrument(
          jax.jit(scatter, donate_argnums=(0,)), 'serve_store_update')
    record_dispatch('serve_store_update')
    self._emb = self._scatter(self._emb, jnp.asarray(idx),
                              jnp.asarray(vals))


class TieredEmbeddingStore:
  """Beyond-HBM embedding store: the materialized table lives in a
  ``storage.TieredFeature`` (HBM hot prefix -> host RAM -> disk), so an
  O(N·F) embedding table larger than device memory still serves — hot
  rows at HBM gather speed, cold rows through the tiered mixed gather
  (pow2 cold blocks, promoted-row warming). The natural pairing is
  ``EmbeddingMaterializer(..., spill_dir=...)`` +
  ``materializer.tiered_embedding_store(...)``.

  Immutable, like DistEmbeddingStore: stale rows are refreshed by
  rematerializing and rotating the spill (docs/serving.md), not by
  in-place scatter — the hot tier is device-resident while warm/disk
  rows are host-resident, and a write-through across tiers would race
  the staging pipeline.
  """

  granularity = 1

  def __init__(self, tiered_feature, num_nodes: Optional[int] = None):
    self.tf = tiered_feature
    self.num_nodes = int(num_nodes if num_nodes is not None
                         else tiered_feature.size)
    self._mask_fn = None

  @property
  def feature_dim(self) -> int:
    return int(self.tf.shape[1])

  def lookup(self, ids, mask):
    """[cap] padded host ids (-1 pads) -> [cap, F] device rows. The
    tiered gather ships only the non-hot rows (UnifiedTensor mixed
    path); one extra jitted where() zeroes the pad slots like
    EmbeddingStore.lookup."""
    import jax
    import jax.numpy as jnp
    rows = self.tf[np.asarray(ids)]
    if self._mask_fn is None:
      from ..metrics import programs
      self._mask_fn = programs.instrument(
          jax.jit(lambda r, m: jnp.where(m[:, None], r, 0)),
          'serve_lookup')
    record_dispatch('serve_lookup')
    return self._mask_fn(rows, jnp.asarray(mask))

  def fetch(self, rows) -> np.ndarray:
    return np.asarray(rows)

  def update_rows(self, ids, rows):
    raise NotImplementedError(
        'TieredEmbeddingStore rows are immutable — rematerialize with '
        'EmbeddingMaterializer(..., spill_dir=...) and rotate the '
        'spill (docs/storage.md, docs/serving.md)')


class DistEmbeddingStore:
  """Sharded embedding store over a mesh: a ``DistFeature`` whose rows
  are the materialized embeddings — the hot-embedding cache IS the
  DistFeature replicated hot split (``split_ratio``/``cache_rows`` +
  ``hotness``), and every lookup is its one-dispatch cached miss-only
  exchange. Bucket capacities must be multiples of the partition count
  (``granularity``): the engine spreads each padded bucket
  ``[cap] -> [P, cap/P]`` so the lookup itself load-balances over the
  serving shards."""

  def __init__(self, dist_feature):
    self.df = dist_feature
    self.granularity = int(dist_feature.num_partitions)
    self.num_nodes = int(dist_feature.feature_pb.shape[0])

  @classmethod
  def build(cls, embeddings, mesh, *, split_ratio: float = 0.0,
            cache_rows: Optional[int] = None, hotness=None,
            wire_dtype=None, bucket_frac=2.0,
            num_nodes: Optional[int] = None):
    """Partition a materialized [N(_pad), F] table into a DistFeature
    over ``mesh`` (contiguous row blocks). PASS ``num_nodes`` for
    materializer tables — it trims the block-padding rows, which would
    otherwise count as servable node ids past the real graph (the same
    footgun ``EmbeddingMaterializer.embedding_store`` closes on the
    single-replica path; prefer its ``dist_embedding_store``).
    ``split_ratio``/``cache_rows``/``hotness`` select the replicated
    hot-embedding cache exactly as the training-time feature cache
    does (docs/feature_cache.md)."""
    from ..distributed.dist_feature import DistFeature
    emb = np.asarray(embeddings)
    if num_nodes is not None:
      emb = emb[:num_nodes]
    n = emb.shape[0]
    p = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    pb = np.minimum((np.arange(n, dtype=np.int64) * p) // max(n, 1),
                    p - 1).astype(np.int32)
    parts = []
    for i in range(p):
      ids = np.where(pb == i)[0].astype(np.int64)
      if ids.size == 0:   # more shards than rows: keep a dummy row
        ids = np.zeros((1,), np.int64)
      parts.append((ids, emb[ids]))
    df = DistFeature(p, parts, pb, mesh=mesh, split_ratio=split_ratio,
                     cache_rows=cache_rows, hotness=hotness,
                     wire_dtype=wire_dtype, bucket_frac=bucket_frac)
    return cls(df)

  @property
  def feature_dim(self) -> int:
    return int(self.df.feature_dim)

  def lookup(self, ids, mask):
    """[cap] padded ids -> [P, cap/P, F] sharded device rows (reshaped
    back to [cap, F] by :meth:`fetch`). DistFeature.get is the one
    dispatch and records it."""
    import jax.numpy as jnp
    ids = jnp.asarray(ids, jnp.int32)
    cap = int(ids.shape[0])
    p = self.granularity
    assert cap % p == 0, (
        f'bucket capacity {cap} must be a multiple of the partition '
        f'count {p} (engine bucket calibration)')
    return self.df.get(ids.reshape(p, cap // p),
                       jnp.asarray(mask).reshape(p, cap // p))

  def fetch(self, rows) -> np.ndarray:
    out = np.asarray(rows)
    return out.reshape(-1, out.shape[-1])

  def publish_stats(self):
    """Per-interval hot-embedding cache hit/miss surfacing — the same
    once-per-epoch fetch discipline as the training feature cache."""
    return self.df.publish_stats()

  def update_rows(self, ids, rows):
    raise NotImplementedError(
        'DistEmbeddingStore rows are immutable — stale nodes are '
        'refreshed on the materializing replica and the sharded store '
        'is rebuilt on rotation/failover (docs/serving.md)')
