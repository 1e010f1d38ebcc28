"""Sharded distributed feature store: hot-vertex cache + miss-only exchange.

TPU-native re-design of
/root/reference/graphlearn_torch/python/distributed/dist_feature.py. The
reference splits a lookup into a local UVA gather plus per-remote-partition
async RPCs and stitches futures (dist_feature.py:134-269). Here the whole
lookup is ONE jitted SPMD function; the asyncio machinery dissolves.

Byte posture (this file owns the largest per-batch wire volume in the
system — feature rows are ~F x wider than sampler id traffic, PERF.md
"Feature path"):

  1. **Replicated hot cache** (GLT's UnifiedTensor split, SURVEY
     §UnifiedTensor; reference data/feature.py split_ratio + hotness
     reorder): the globally hottest ``cache_rows`` rows live replicated on
     every shard next to its owned partition. Requested ids are split
     hit/miss INSIDE the program by two reads of a rank directory over
     the cached ids (ops/rank_directory.py: a word of membership bits
     and the rank below it); hits gather locally and never touch the
     interconnect.
  2. **Miss-only bucketed exchange**: only cache misses — deduped within
     the batch (one request per unique id, response scattered back to all
     its slots) — enter the all_to_all, packed into per-destination
     buckets of capacity ``bucket_frac x mean miss load`` with the
     psum-replicated ``lax.cond`` full-width fallback (exactly the
     sampler-exchange contract: loss-free on EVERY input,
     dist_neighbor_sampler._exchange_hop). On a 2-axis ('slice', 'chip')
     mesh the transposes go hierarchical: full-width along 'chip' (ICI),
     fractional along 'slice' (DCN), retraced for the response. The
     OWNERS' side is bounded by what the buckets hold, not by their
     capacity: ``ops.route_slots`` ranks a request within its
     destination, so a received bucket is a valid prefix followed by
     pads, and :func:`bounded_lookup` runs the lookup in ``feat_ids`` and
     the row gather tile by tile below the received block's last valid
     column (a loss-free bucket is mostly pads: 8.5 % full in the mesh
     benchmark cell). No capacity, wire byte or overflow rule changes;
     the rows that leave a shard are the one-piece lookup's bit for bit.
  3. **Wire dtype**: ``wire_dtype=jnp.bfloat16`` ships response rows at
     half width and upcasts to the storage dtype after
     ``gather_from_buckets`` — independent of hit rate.

Every position in the sorted ``feat_ids`` — the owners' lookup of the
miss buckets, set-up's fill of the cache — is found through the table's
two-level index (ops/sorted_index.py). Both the index and the cache's
directory are built once when the store is, on the host for host tables
and on the devices for device shards, and handed to the programs beside
the rows they lead to.

On-device hit/miss/overflow counters ride the same program (a [P, 4]
accumulator threaded through every ``get``), so hit rates are observable
with ZERO per-batch host syncs: fetch with :meth:`stats` /
:meth:`publish_stats` once per epoch.
"""
from typing import Optional

import numpy as np

from .. import ops
from ..ops import rank_directory, sorted_index
from ..metrics.registry_names import (SCOPE_CACHE, SCOPE_COLLATE,
                                      SCOPE_DEDUP, SCOPE_EXCHANGE,
                                      SCOPE_FANOUT, SCOPE_LOOKUP,
                                      SCOPE_PACK, SCOPE_ROUTE, SCOPE_ROWS,
                                      SCOPE_TILE, SCOPE_UNPACK, SCOPE_WIRE)
from ..ops.neighbor import draw_tile_rows, tiles_to_run
from ..ops.route import exchange_capacity

INT32_MAX = np.iinfo(np.int32).max

# stats accumulator layout (per shard, int32)
STAT_HITS, STAT_MISSES, STAT_UNIQUE, STAT_OVERFLOW = range(4)


def miss_capacity(request_width: int, nparts: int, bucket_frac,
                  hit_rate: float = 0.0) -> int:
  """Static per-destination bucket capacity for a miss-only feature
  exchange over ``request_width`` request slots: ``bucket_frac x`` the
  mean per-destination MISS load (the expected unique-miss width is
  ``request_width * (1 - hit_rate)``), rounded to lanes and clamped to
  the loss-free full width. ``bucket_frac=None`` keeps the full-width
  posture (every bucket ``request_width`` wide, can never overflow).
  Thin front of the shared capacity policy in ops.route —
  the sampler's exchange resolves through the same function."""
  return exchange_capacity(request_width, nparts, bucket_frac, hit_rate)


def feature_exchange_mb(request_width: int, nparts: int, feat_dim: int,
                        bucket_frac=2.0, wire_bytes: int = 4,
                        id_bytes: int = 4, hit_rate: float = 0.0) -> float:
  """Analytic all_to_all MB/shard/batch of one distributed feature
  lookup: [P, cap] id requests + [P, cap, F] row responses. The
  full-width posture (the pre-cache baseline) is ``bucket_frac=None,
  wire_bytes=4, hit_rate=0``. Benchmarks report this next to measured
  volumes so byte regressions are visible without a trace."""
  cap = miss_capacity(request_width, nparts, bucket_frac, hit_rate)
  return nparts * cap * (id_bytes + feat_dim * wire_bytes) / 1e6


def _part(name: str, tiled: bool = False):
  """The scope of one part of the row exchange. In a tile of
  :func:`bounded_lookup`'s loop the ``tile`` component stands BEHIND the
  part's name (``lookup/tile``, ``rows/tile``): a reader files the work
  under its part and counts the loop's executions by the component."""
  import jax
  return jax.named_scope(f'{name}/{SCOPE_TILE}' if tiled else name)


def bounded_lookup(lookup, r, fdim: int, wdtype):
  """The owners' side of the row exchange over a received request block
  ``r [P, cap]`` (ids, any negative a pad): ``rows [P, cap, F]`` at the
  wire dtype, zeros where ``lookup`` finds nothing, bounded by what the
  buckets hold. ``lookup(flat [n], tiled) -> rows [n, F]`` is a
  ``lookup_local`` bound to its tables.

  A block wide enough to tile (``ops.neighbor.draw_tile_rows`` of its
  ``cap``, the draw's one rule) is looked up in tiles of ``T`` columns,
  ``ceil(last valid column / T)`` of them: only the tiles that begin
  below its last valid column. ``ops.route_slots`` ranks a request
  within its destination, so every received bucket is a valid prefix
  followed by pads, and a bucket sized to be loss-free is mostly pads.
  Any mask is exact, prefix or not: columns of tiles not run keep the
  zeros a pad reads. The last tile is clamped to end at the cap, so it
  may redo columns of the one before — to the same values. The trip
  count is this shard's own: no collective may sit inside the loop. A
  block narrower than the rule's threshold is looked up in one piece."""
  import jax
  import jax.numpy as jnp
  nb, cap = r.shape
  t = draw_tile_rows(cap)
  if not t:
    rows = lookup(r.reshape(-1), False)
    with _part(SCOPE_ROWS):
      return rows.astype(wdtype).reshape(nb, cap, fdim)
  assert 0 < t <= cap, (t, cap)
  with _part(SCOPE_LOOKUP):
    tiles = tiles_to_run(r >= 0, t)

  def body(i, out):
    lo = jnp.minimum(i * t, cap - t)
    with _part(SCOPE_LOOKUP, True):
      flat = jax.lax.dynamic_slice(r, (0, lo), (nb, t)).reshape(-1)
    rows = lookup(flat, True)
    with _part(SCOPE_ROWS, True):
      return jax.lax.dynamic_update_slice(
          out, rows.astype(wdtype).reshape(nb, t, fdim), (0, lo, 0))

  with _part(SCOPE_ROWS):
    # inside shard_map the carry varies over the axes the block does
    # (the v5e's compiler rebuilds this constant fill without its name:
    # a timeline files it as unscoped, PERF.md section 5)
    init = jax.lax.pcast(jnp.zeros((nb, cap, fdim), wdtype),
                         tuple(jax.typeof(r).vma), to='varying')
  return jax.lax.fori_loop(0, tiles, body, init)


def _hot_ids_fn(h: int):
  """``DistFeature._hot_ids`` as a program over a device score vector
  (int32 or float32, no NaN): the ``h`` hottest ids, ascending, ties to
  the lower id. No sort: the ``h``-th largest score is found by 32
  halvings over the scores' order-preserving bit patterns, then every id
  above it and the lowest ids that tie with it are taken in id order (a
  sort of N keys costs the TPU's compiler half a minute at N = 37 M)."""
  import jax
  import jax.numpy as jnp
  from jax import lax

  def pick(hot):
    hot = hot.reshape(-1)
    if jnp.issubdtype(hot.dtype, jnp.floating):
      # -0.0 ties with 0.0, as it does for the host's sort
      hot = jnp.where(hot == 0, 0, hot).astype(jnp.float32)
      bits = lax.bitcast_convert_type(hot, jnp.uint32)
      key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    else:
      key = lax.bitcast_convert_type(hot.astype(jnp.int32),
                                     jnp.uint32) ^ jnp.uint32(1 << 31)

    def halve(i, t):
      # keep the bit where at least h keys still reach the threshold
      up = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
      return jnp.where(jnp.sum(key >= up) >= h, up, t)

    t = lax.fori_loop(0, 32, halve, jnp.uint32(0))   # h-th largest key
    above = key > t
    ties = key == t
    room = h - jnp.sum(above)
    take = above | (ties & (jnp.cumsum(ties, dtype=jnp.int32) <= room))
    return jnp.nonzero(take, size=h)[0].astype(jnp.int32)

  return jax.jit(pick)


def _gather_replicated_fn(mesh, dtype, shift: int, depth: int):
  """The program ``(feat_ids [P, n], feat_starts [P, S], feats
  [P, n, F], ids [m]) -> rows [m, F]`` replicated on every device of the
  mesh, out of row shards on the mesh: each shard looks the ids up in its
  own sorted id table (through its index, ``shift`` / ``depth``), and
  one ``psum`` of the rows' BIT PATTERNS (every shard but the owner adds
  zero bits, so the sum is the owner's row to the bit, a negative zero
  included) replicates them."""
  import jax
  import jax.numpy as jnp
  from jax.sharding import PartitionSpec as P

  from ..utils.compat import shard_map
  ax = tuple(mesh.axis_names)
  bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[
      jnp.dtype(dtype).itemsize]

  def body(fid, starts, f, ids):
    found, pos = sorted_index.indexed_membership(fid[0], starts[0], ids,
                                                 shift, depth)
    rows = jax.lax.bitcast_convert_type(f[0, pos], bits)
    rows = jnp.where(found[:, None], rows, jnp.zeros((), bits))
    return jax.lax.bitcast_convert_type(jax.lax.psum(rows, ax), f.dtype)

  return jax.jit(shard_map(body, mesh=mesh,
                           in_specs=(P(ax), P(ax), P(ax), P()),
                           out_specs=P(), check_replication=False))


class DistFeature:
  """Reference: dist_feature.py:51-269.

  Args:
    num_partitions: partitions == product of the mesh axis sizes.
    feat_parts: list of (ids [n_p], feats [n_p, F]) per partition (the
      FeaturePartitionData payload, cache already merged via
      cat_feature_cache).
    feature_pb: [N] id -> owning partition (the *feature* partition book —
      may differ from the graph node_pb once caches move entries).
    mesh: the graph mesh ('g',) flat or ('slice', 'chip') hierarchical.
    dtype: optional storage dtype (bf16 halves HBM + ICI bytes).
    split_ratio: fraction of the N globally hottest rows replicated
      per shard (0 = no cache, 1 = fully replicated), mirroring the
      local ``data.Feature`` API.
    cache_rows: absolute row count for the hot cache (overrides
      ``split_ratio``).
    hotness: [N] per-id hotness score (higher = hotter) selecting the
      cached set — in-degrees (``data.reorder.in_degree_hotness``) or a
      presampling frequency count (``data.reorder.frequency_hotness``).
      None assumes ids are already hot-ordered (row 0 hottest), the
      layout ``data.reorder.sort_by_in_degree`` produces.
    wire_dtype: optional dtype for response rows ON THE WIRE (e.g.
      jnp.bfloat16); storage and results stay ``dtype``.
    bucket_frac: miss-exchange bucket slack over the mean miss load
      (None = full-width loss-free posture, the pre-cache baseline).
    dedup: dedup misses within the batch before the exchange (one
      request per unique id; the response fans back to every slot).
  """

  def __init__(self, num_partitions: int, feat_parts, feature_pb,
               mesh=None, dtype=None, split_ratio: float = 0.0,
               cache_rows: Optional[int] = None, hotness=None,
               wire_dtype=None, bucket_frac=2.0, dedup: bool = True):
    self.num_partitions = num_partitions
    self.feature_pb = np.asarray(feature_pb)
    self.mesh = mesh
    self._init_storage(feat_parts, dtype)
    self._init_lookup(split_ratio, cache_rows, wire_dtype, bucket_frac,
                      dedup)
    h = self.cache_rows
    n_total = int(self.feature_pb.shape[0])
    if h > 0:
      self.cache_ids = self._hot_ids(hotness, h)
      self.cache_feats = self.cpu_get(self.cache_ids)
      self._cache_dir = rank_directory.build_directory_host(self.cache_ids,
                                                            n_total)
    else:
      self.cache_ids = None
      self.cache_feats = None
    self._set_indexes(
        sorted_index.build_sorted_index_host(self.feat_ids, n_total))

  def _init_lookup(self, split_ratio, cache_rows, wire_dtype, bucket_frac,
                   dedup):
    """The lookup's configuration, shared by both constructors: the
    cache's size and what sizes the miss buckets."""
    self.split_ratio = float(split_ratio)
    self.wire_dtype = wire_dtype
    self.bucket_frac = bucket_frac
    self.dedup = dedup
    n_total = int(self.feature_pb.shape[0])
    h = int(cache_rows) if cache_rows is not None \
        else int(n_total * self.split_ratio)
    self.cache_rows = max(0, min(h, n_total))
    # hit-rate floor used to size the miss buckets: uniform requests hit
    # at exactly H/N; skewed-to-hot requests (the point of the cache)
    # hit more, so capacities sized on (1 - H/N) only gain slack
    self._cache_frac = self.cache_rows / n_total if n_total else 0.0
    # the family this store's counters and gauges go under; a sampler's
    # label store is told 'dist_label' (DistNeighborSampler._label_dist)
    self.stats_prefix = 'dist_feature'
    self._dev = None
    self._stats = None
    self._fns = {}

  def _cache_tables(self):
    """The host cache ``((words [W], rank [W]), rows [H, F])``: its rank
    directory and its rows. A store with no cache feeds its programs a
    one-entry pad directory and row, which no lookup reads."""
    if self.cache_rows:
      return self._cache_dir, self.cache_feats
    return ((np.zeros((1,), np.uint32), np.zeros((1,), np.int32)),
            np.zeros((1, self.feature_dim), self.storage_dtype))

  def _set_indexes(self, row_index, shared_rows=False):
    """Keep the two-level index of ``feat_ids``
    (``ops.sorted_index.SortedIndex``: the lookup programs are traced
    with its ``shift`` and ``depth``) and publish what THIS store built
    — the halvings a row lookup runs, and the bytes a chip holds for
    that index and for the cache's rank directory
    (``ops.rank_directory``: 4 bytes an entry). Set once here, never per
    batch."""
    from .. import metrics
    self._row_index = row_index
    nbytes = 0
    if not shared_rows:
      metrics.set_gauge('dist_feature.index_depth.rows', row_index.depth)
      nbytes += 4 * int(row_index.starts.shape[-1])
    if self.cache_rows:
      nbytes += 4 * sum(rank_directory.directory_sizes(
          self.feature_pb.shape[0]))
    if nbytes:
      metrics.set_gauge('dist_feature.index_bytes', nbytes)

  def _hot_ids(self, hotness, h: int) -> np.ndarray:
    """The ``h`` hottest ids, ascending: the first ``h`` of a stable
    descending sort of ``hotness`` (ties to the lower id); the lowest
    ids where there is no score."""
    n_total = int(self.feature_pb.shape[0])
    if hotness is None:
      return np.arange(h, dtype=np.int32)
    hotness = np.asarray(hotness).reshape(-1)
    assert hotness.shape[0] == n_total, (
        f'hotness covers {hotness.shape[0]} ids, feature_pb has '
        f'{n_total}')
    return np.sort(np.argsort(-hotness, kind='stable')[:h]).astype(np.int32)

  @classmethod
  def from_device_shards(cls, mesh, feature_pb, feat_ids, feats,
                         split_ratio: float = 0.0,
                         cache_rows: Optional[int] = None, hotness=None,
                         wire_dtype=None, bucket_frac=2.0,
                         dedup: bool = True, pb_dev=None,
                         row_index=None):
    """A store over row shards that ALREADY live on their devices:
    ``feat_ids`` ``[P, n_max]`` (each shard's owned ids ascending,
    INT32_MAX-padded) and ``feats`` ``[P, n_max, F]`` (its rows in that
    order), both sharded on their leading axis over ``mesh`` — the
    arrays :meth:`device_arrays` of a host-built store uploads, bit for
    bit. No ``[N, F]`` array is ever in host memory: a row store too
    large to pack on the host (``_init_storage`` holds it once, the
    caller's parts a second time) is generated or loaded shard by shard
    straight onto the mesh.

    The hot cache is filled ON the devices: ``hotness`` is an ``[N]``
    score vector (in-degree; a host or a device array), the hottest
    ``cache_rows`` ids are selected as the host constructor selects them
    (stable descending sort, ties to the lower id), and every shard
    contributes the cached rows it owns to a replicated ``[H, F]`` table
    through one ``psum`` of the rows' bit patterns (exact: all shards
    but the owner add zero). ``feature_pb`` stays a host array (the
    routing book; ``pb_dev`` is its replicated placement where the
    caller already made it); :meth:`cpu_get` fetches from the devices.
    The two-level index of ``feat_ids`` and the cache's rank directory
    are built on the devices too; ``row_index`` is another store's
    ``_row_index`` over the SAME ``feat_ids`` (a label store shares the
    feature store's, as it shares the book)."""
    import jax
    self = cls.__new__(cls)
    self.num_partitions = int(feat_ids.shape[0])
    self.feature_pb = np.asarray(feature_pb)
    self.mesh = mesh
    self.n_max = int(feats.shape[1])
    self._fdim = int(feats.shape[2])
    self.storage_dtype = np.dtype(feats.dtype)
    self.feat_ids = self.feats = None      # no host copy of the shards
    self._init_lookup(split_ratio, cache_rows, wire_dtype, bucket_frac,
                      dedup)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..utils import global_device_put
    ax = tuple(mesh.axis_names)
    shard = NamedSharding(mesh, P(ax))
    repl = NamedSharding(mesh, P())
    for name, a in (('feat_ids', feat_ids), ('feats', feats)):
      if not a.sharding.is_equivalent_to(shard, a.ndim):
        raise ValueError(
            f'DistFeature.from_device_shards: {name} is placed '
            f'{a.sharding}, not sharded on its leading axis over the '
            f'mesh ({shard})')
    from ..utils.trace import record_dispatch
    n_total = int(self.feature_pb.shape[0])
    shared_rows = row_index is not None

    if not shared_rows:
      record_dispatch('dist_feature.build_index')
      row_index = sorted_index.build_sorted_index_shards(mesh, feat_ids,
                                                         n_total)
    h = self.cache_rows
    if h > 0:
      if hotness is None or not isinstance(hotness, jax.Array):
        cache_ids = global_device_put(self._hot_ids(hotness, h), repl)
      else:
        record_dispatch('dist_feature.fill_cache')
        cache_ids = _hot_ids_fn(h)(jax.device_put(hotness, repl))
      record_dispatch('dist_feature.fill_cache')
      cache_feats = _gather_replicated_fn(
          mesh, feats.dtype, row_index.shift, row_index.depth)(
              feat_ids, row_index.starts, feats, cache_ids)
      # one program over the replicated cache ids
      record_dispatch('dist_feature.build_index')
      words, rank = jax.jit(
          lambda t: rank_directory.build_directory(t, n_total))(cache_ids)
      self.cache_ids = np.asarray(cache_ids)
    else:
      (words, rank), cache_feats = jax.tree.map(
          lambda a: global_device_put(a, repl), self._cache_tables())
      self.cache_ids = None
    self.cache_feats = None                # on the devices only
    self._set_indexes(row_index, shared_rows)
    self._dev = dict(
        feat_ids=feat_ids, feat_starts=row_index.starts, feats=feats,
        feature_pb=pb_dev if pb_dev is not None else global_device_put(
            self.feature_pb.astype(np.int32), repl),
        cache_words=words, cache_rank=rank, cache_feats=cache_feats)
    return self

  def _init_storage(self, feat_parts, dtype):
    """Pack the per-partition (ids, rows) blocks into the sorted
    [P, n_max] id table + the [P, n_max, F] row store. The row store
    is HOST-RAM-resident here; storage.TieredDistFeature overrides
    this to keep rows in memory-mapped disk tiers (the out-of-core
    shard layout, docs/storage.md) while the id table — the small
    routing structure — stays resident."""
    n_max = max(ids.shape[0] for ids, _ in feat_parts)
    f = feat_parts[0][1].shape[1]
    p = len(feat_parts)
    dt = np.dtype(dtype or feat_parts[0][1].dtype)
    self.n_max = n_max
    self._fdim = int(f)
    self.storage_dtype = dt
    self.feat_ids = np.full((p, n_max), INT32_MAX, np.int32)
    self.feats = np.zeros((p, n_max, f), dt)
    for i, (ids, fe) in enumerate(feat_parts):
      order = np.argsort(ids)
      self.feat_ids[i, :ids.shape[0]] = ids[order]
      self.feats[i, :ids.shape[0]] = fe[order]

  @property
  def feature_dim(self) -> int:
    return self._fdim

  def device_arrays(self):
    if self._dev is None:
      from jax.sharding import NamedSharding, PartitionSpec as P
      from ..utils import global_device_put
      shard = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
      self._dev = dict(feats=global_device_put(self.feats, shard),
                       **self._routing_arrays())
    return self._dev

  def _routing_arrays(self) -> dict:
    """A host-built store's routing structures placed on the mesh: the
    sorted id tables and their index, the book, the hot cache and its
    directory — everything a lookup program takes but the row payload."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..utils import global_device_put
    shard = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
    repl = NamedSharding(self.mesh, P())
    (words, rank), cache_feats = self._cache_tables()
    return dict(
        feat_ids=global_device_put(self.feat_ids, shard),
        feat_starts=global_device_put(self._row_index.starts, shard),
        feature_pb=global_device_put(self.feature_pb.astype(np.int32),
                                     repl),
        cache_words=global_device_put(words, repl),
        cache_rank=global_device_put(rank, repl),
        cache_feats=global_device_put(cache_feats, repl))

  # device_arrays()' entries a lookup program takes, by placement
  SHARD_KEYS = ('feat_ids', 'feat_starts', 'feats')
  REPL_KEYS = ('feature_pb', 'cache_words', 'cache_rank', 'cache_feats')

  @staticmethod
  def table_args(shard_view: dict, repl_view: dict, feats=None) -> tuple:
    """The five table arguments of a :meth:`_shard_body` program out of
    per-shard views of :meth:`device_arrays`' entries: rows travel with
    what finds their position, ``(starts, rows)`` behind the sorted
    ``feat_ids`` and ``(rank, rows)`` behind the cache's membership
    words. ``feats`` replaces the row payload (the slab path's ``(hot,
    slab_pos, slab_rows)``)."""
    if feats is None:
      feats = shard_view['feats']
    return (shard_view['feat_ids'], (shard_view['feat_starts'], feats),
            repl_view['feature_pb'], repl_view['cache_words'],
            (repl_view['cache_rank'], repl_view['cache_feats']))

  # ------------------------------------------------------------ stats
  def _stats_dev(self):
    if self._stats is None:
      import jax
      from jax.sharding import NamedSharding, PartitionSpec as P
      from ..utils import global_device_put
      shard = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
      self._stats = global_device_put(
          np.zeros((self.num_partitions, 4), np.int32), shard)
    return self._stats

  def stats(self) -> dict:
    """Host snapshot of the on-device counters, summed over shards.

    This is the ONE device->host fetch of the feature path — call it per
    epoch (loaders do), never per batch. On a multi-host mesh only this
    process's shard rows are fetched (a global np.asarray would span
    non-addressable devices and raise) — counters are per-shard disjoint
    rows of the [P, 4] accumulator, so the result is the process-local
    view; aggregate across hosts out of band if needed."""
    if self._stats is None:
      tot = np.zeros((4,), np.int64)
    elif getattr(self._stats, 'is_fully_addressable', True):
      tot = np.asarray(self._stats).sum(axis=0).astype(np.int64)
    else:
      tot = sum(np.asarray(s.data).reshape(-1, 4).sum(axis=0)
                for s in self._stats.addressable_shards).astype(np.int64)
    lookups = int(tot[STAT_HITS] + tot[STAT_MISSES])
    return dict(hits=int(tot[STAT_HITS]), misses=int(tot[STAT_MISSES]),
                unique_misses=int(tot[STAT_UNIQUE]),
                overflow=int(tot[STAT_OVERFLOW]), lookups=lookups,
                hit_rate=(int(tot[STAT_HITS]) / lookups if lookups
                          else 0.0))

  def reset_stats(self):
    self._stats = None

  def publish_stats(self, prefix: str = 'dist_feature'):
    """Fetch + reset the on-device counters into utils.trace named
    counters ('<prefix>.hits' etc.) — the per-epoch surfacing hook."""
    from ..utils import trace
    s = self.stats()
    for k in ('hits', 'misses', 'unique_misses', 'overflow', 'lookups'):
      if s[k]:
        # graftlint: allow[metric-registry] caller-chosen prefix; both families (dist_feature.*/dist_label.*) are registered wildcards
        trace.counter_inc(f'{prefix}.{k}', s[k])
    self.reset_stats()
    return s

  # ---------------------------------------------------------- program
  def _lookup_fn(self, slab: bool = False):
    """``lookup_local(feat_ids [n], (feat_starts, feats), flat [m],
    tiled=False) -> rows [m, F]`` of :meth:`_shard_body`: the rows of a
    flat request vector over this shard's sorted owned ids, zeros where
    absent or padded. ``tiled`` says the call is one tile of
    :func:`bounded_lookup`'s loop (:func:`_part` names it so)."""
    import jax.numpy as jnp
    _, rshift, rdepth = self._row_index

    if slab:
      def lookup_local(feat_ids, feats, flat, tiled=False):
        """Slab-backed rows for a flat request vector: position from
        the sorted owned-id table as usual, payload from the hot
        prefix or the staged slab (zeros where absent/padded — an
        impossible case for planned rows under an exact program)."""
        starts, (hot, slab_pos, slab_rows) = feats
        with _part(SCOPE_LOOKUP, tiled):
          found, pos = sorted_index.indexed_membership(
              feat_ids, starts, flat, rshift, rdepth)
        hp = hot.shape[0]
        with _part(SCOPE_ROWS, tiled):
          hot_rows = hot[jnp.clip(pos, 0, hp - 1)]
        with _part(SCOPE_LOOKUP, tiled):
          sp = jnp.clip(jnp.searchsorted(slab_pos, pos.astype(jnp.int32)),
                        0, slab_pos.shape[0] - 1)
          in_slab = slab_pos[sp] == pos.astype(jnp.int32)
        with _part(SCOPE_ROWS, tiled):
          rows = jnp.where((pos < hp)[:, None], hot_rows,
                           jnp.where(in_slab[:, None], slab_rows[sp], 0))
          return jnp.where(found[:, None], rows, 0)
    else:
      def lookup_local(feat_ids, feats, flat, tiled=False):
        """Rows for a flat request vector over this shard's sorted owned
        ids (zeros where absent/padded). ``feats`` may keep shard_map's
        leading ``[1, n, F]`` axis: on a TPU dropping it (``feats[0]``)
        is a physical copy of the whole table in front of every program
        that gathers from it — 4.7 GB at 9 M rows — where indexing
        through it is free."""
        starts, feats = feats
        with _part(SCOPE_LOOKUP, tiled):
          found, pos = sorted_index.indexed_membership(
              feat_ids, starts, flat, rshift, rdepth)
        with _part(SCOPE_ROWS, tiled):
          rows = feats[0, pos] if feats.ndim == 3 else feats[pos]
          return jnp.where(found[:, None], rows, 0)
    return lookup_local

  def _shard_body(self, b: int, slab: bool = False):
    """Per-shard lookup body over UNWRAPPED per-shard views — the core
    of the one-dispatch program, exposed so outer shard_map programs
    (DistScanTrainer's scanned epoch) can inline the exact same
    cache-split -> miss-dedup -> bucketed-exchange -> merge computation
    and thread the [4] stats row through their own carry.

    Returns ``body(feat_ids [n], (feat_starts, feats [n, F] or
    [1, n, F]), pb, cache_words, (cache_rank, cache_feats), stats_row
    [4], ids [b], mask [b]) -> (rows [b, F], new_stats_row [4])``: the
    rows of each table arrive behind what finds their position
    (:meth:`table_args` packs the five table arguments from
    :meth:`device_arrays`' entries). Must be traced on this store's mesh
    (the exchange collectives run over every mesh axis).

    ``slab=True`` is the SLAB-BACKED lookup path (device
    oversubscription through the shard exchange — storage/dist_scan.py,
    docs/storage.md): ``feats`` is then the pytree ``(hot [H, F],
    slab_pos [cap], slab_rows [cap, F])`` instead of the full
    ``[n, F]`` partition — a remote request resolves its position in
    this shard's sorted id table exactly as before, but the ROW comes
    from the HBM hot prefix (position < H) or the chunk's staged slab
    (searchsorted over the staged position list, which has no id space
    to index; INT32_MAX pads never match). Under an exact miss-exchange
    program every requested position >= H is in the slab by
    construction, so the exchanged bytes are identical to the all-HBM
    path."""
    import jax
    import jax.numpy as jnp

    nparts = self.num_partitions
    fdim = self.feature_dim
    fdtype = self.storage_dtype
    wdtype = self.wire_dtype or fdtype
    h = self.cache_rows
    dedup = self.dedup
    bucket_frac = self.bucket_frac
    hit_est = self._cache_frac
    # collectives/specs over every mesh axis: works identically on the
    # flat ('g',) mesh and a 2-axis ('slice', 'chip') mesh
    ax = tuple(self.mesh.axis_names)
    sizes = tuple(self.mesh.shape[a] for a in ax)
    hier = len(ax) == 2
    # the miss buckets' capacity on the no-overflow path, and the request
    # slots a shard packs, sends and returns through it a step: every
    # bucket of the flat exchange, the 'slice' stage's of the hierarchical
    # one (sized on the mean VALID miss load, ~miss width over S, not the
    # C*b slot count). Its owners look the block up a tile at a time
    # (bounded_lookup): the slots a tile holds, 0 where the block is too
    # narrow to tile. Both published once here, never per batch; the fill
    # ratio is unique_misses over the slots (docs/observability.md)
    if hier:
      s_sz, c_sz = sizes
      cap2 = (c_sz * b if bucket_frac is None or s_sz <= 1 else
              min(c_sz * b,
                  miss_capacity(b, s_sz, bucket_frac, hit_est)))
      buckets, cap = s_sz, cap2
    else:
      cap_small = miss_capacity(b, nparts, bucket_frac, hit_est)
      buckets, cap = nparts, cap_small
    from .. import metrics
    # graftlint: allow[metric-registry] the store's family (dist_feature.* / dist_label.*, both registered wildcards)
    metrics.set_gauge(f'{self.stats_prefix}.exchange_slots', buckets * cap)
    # graftlint: allow[metric-registry] the store's family, as above
    metrics.set_gauge(f'{self.stats_prefix}.lookup_tile_slots',
                      buckets * draw_tile_rows(cap))

    lookup_local = self._lookup_fn(slab)

    def owner_rows(feat_ids, feats, r):
      """The rows ``[P, cap, F]`` (wire dtype) this shard owns of a
      received request block ``r [P, cap]``."""
      return bounded_lookup(
          lambda flat, tiled: lookup_local(feat_ids, feats, flat, tiled),
          r, fdim, wdtype)

    def exchange_flat(feat_ids, feats, pb, req, rmask):
      """Fractional bucketed all_to_all with replicated full-width
      fallback (sampler _exchange_hop parity). Returns rows [b, F]
      (storage dtype) in request order + the overflow count."""
      with jax.named_scope(SCOPE_ROUTE):
        dest = jnp.where(rmask, pb[jnp.maximum(req, 0)], nparts)
        slot, ok = ops.route_slots(dest, rmask, capacity=b)

      def do(cap: int):
        with jax.named_scope(SCOPE_PACK):
          okc = ok & (slot < cap)
          send = ops.scatter_to_buckets(req, dest, slot, okc, nparts, cap)
        with jax.named_scope(SCOPE_WIRE):
          r = jax.lax.all_to_all(send, ax, 0, 0)        # [P, cap] reqs
        rows = owner_rows(feat_ids, feats, r)
        with jax.named_scope(SCOPE_WIRE):
          resp = jax.lax.all_to_all(rows, ax, 0, 0)     # [P, cap, F]
        with jax.named_scope(SCOPE_UNPACK):
          back = ops.gather_from_buckets(resp, dest, slot, okc, fill=0)
          return back.astype(fdtype)

      if cap_small >= b:
        return do(b), jnp.int32(0)
      with jax.named_scope(SCOPE_ROUTE):
        ovf = jnp.sum(rmask & (slot >= cap_small)).astype(jnp.int32)
        total_ovf = jax.lax.psum(ovf, ax)
      rows = jax.lax.cond(total_ovf == 0, lambda _: do(cap_small),
                          lambda _: do(b), None)
      return rows, ovf

    def exchange_hier(feat_ids, feats, pb, req, rmask):
      """2-stage exchange for a (slice, chip) mesh: full-width along
      'chip' (ICI), fractional along 'slice' (DCN), retraced for the
      response — the feature-row counterpart of
      dist_neighbor_sampler._exchange_hop_hier. Stage-2 capacity is
      sized on the mean VALID miss load (~miss width over S), not the
      C*b slot count."""
      s_ax, c_ax = ax
      with jax.named_scope(SCOPE_ROUTE):
        dest = jnp.where(rmask, pb[jnp.maximum(req, 0)], nparts)
        c_dst = jnp.where(rmask, dest % c_sz, c_sz)
        slot1, ok1 = ops.route_slots(c_dst, rmask, capacity=b)
      with jax.named_scope(SCOPE_PACK):
        send1 = ops.scatter_to_buckets(req, c_dst, slot1, ok1, c_sz, b)
      with jax.named_scope(SCOPE_WIRE):
        req1 = jax.lax.all_to_all(send1, c_ax, 0, 0)    # [C, b] via ICI
      with jax.named_scope(SCOPE_ROUTE):
        mid = req1.reshape(-1)
        mid_mask = mid >= 0
        mdest = jnp.where(mid_mask, pb[jnp.maximum(mid, 0)] // c_sz, s_sz)
        slot2, ok2f = ops.route_slots(mdest, mid_mask, capacity=c_sz * b)

      def hier_path(_):
        with jax.named_scope(SCOPE_PACK):
          ok2 = ok2f & (slot2 < cap2)
          send2 = ops.scatter_to_buckets(mid, mdest, slot2, ok2, s_sz,
                                         cap2)
        with jax.named_scope(SCOPE_WIRE):
          req2 = jax.lax.all_to_all(send2, s_ax, 0, 0)  # [S, cap2] DCN
        rows = owner_rows(feat_ids, feats, req2)
        with jax.named_scope(SCOPE_WIRE):
          r2 = jax.lax.all_to_all(rows, s_ax, 0, 0)
        with jax.named_scope(SCOPE_UNPACK):
          b2 = ops.gather_from_buckets(r2, mdest, slot2, ok2, fill=0)
        with jax.named_scope(SCOPE_WIRE):
          r1 = jax.lax.all_to_all(b2.reshape(c_sz, b, fdim), c_ax, 0, 0)
        with jax.named_scope(SCOPE_UNPACK):
          back = ops.gather_from_buckets(r1, c_dst, slot1, ok1, fill=0)
          return back.astype(fdtype)

      def flat_path(_):
        with jax.named_scope(SCOPE_ROUTE):
          slotp, okp = ops.route_slots(dest, rmask, capacity=b)
        with jax.named_scope(SCOPE_PACK):
          send = ops.scatter_to_buckets(req, dest, slotp, okp, nparts, b)
        with jax.named_scope(SCOPE_WIRE):
          r = jax.lax.all_to_all(send, ax, 0, 0)
        rows = owner_rows(feat_ids, feats, r)
        with jax.named_scope(SCOPE_WIRE):
          resp = jax.lax.all_to_all(rows, ax, 0, 0)
        with jax.named_scope(SCOPE_UNPACK):
          back = ops.gather_from_buckets(resp, dest, slotp, okp, fill=0)
          return back.astype(fdtype)

      if cap2 >= c_sz * b:
        return hier_path(None), jnp.int32(0)
      with jax.named_scope(SCOPE_ROUTE):
        ovf = jnp.sum(mid_mask & (slot2 >= cap2)).astype(jnp.int32)
        total_ovf = jax.lax.psum(ovf, ax)
      rows = jax.lax.cond(total_ovf == 0, hier_path, flat_path, None)
      return rows, ovf

    @jax.named_scope(SCOPE_COLLATE)
    def body(feat_ids, feats, pb, cache_words, cache_feats, stats, ids,
             mask):
      safe = jnp.maximum(ids, 0)
      cache_rank, cache_feats = cache_feats
      with jax.named_scope(SCOPE_CACHE):
        if h > 0:
          with jax.named_scope(SCOPE_LOOKUP):
            in_cache, cpos = rank_directory.directory_membership(
                cache_words, cache_rank, safe, h)
            is_hit = mask & in_cache
          with jax.named_scope(SCOPE_ROWS):
            out_hit = jnp.where(is_hit[:, None], cache_feats[cpos], 0)
          miss = mask & ~is_hit
        else:
          is_hit = jnp.zeros_like(mask)
          out_hit = jnp.zeros((b, fdim), fdtype)
          miss = mask
      with jax.named_scope(SCOPE_EXCHANGE):
        with jax.named_scope(SCOPE_DEDUP):
          if dedup:
            # one request per unique missed id; `inverse` fans the
            # response row back to every batch slot that asked for it
            req, ucnt, inverse = ops.masked_unique(ids, miss, size=b)
            rmask = req != ops.FILL
          else:
            req, rmask = ids, miss
            inverse = jnp.where(miss, jnp.arange(b, dtype=jnp.int32), -1)
            ucnt = jnp.sum(miss)
        exchange = exchange_hier if hier else exchange_flat
        rows, ovf = exchange(feat_ids, feats, pb, req, rmask)
        with jax.named_scope(SCOPE_FANOUT):
          out_miss = rows[jnp.maximum(inverse, 0)]
      out = jnp.where(is_hit[:, None], out_hit.astype(fdtype),
                      jnp.where(miss[:, None], out_miss, 0))
      batch_stats = jnp.stack([
          jnp.sum(is_hit), jnp.sum(miss), ucnt, ovf]).astype(jnp.int32)
      return out, stats + batch_stats

    return body

  def _build_fn(self, b: int):
    """Jitted shard_map lookup for per-shard request blocks of size b:
    cache split -> miss dedup -> bucketed (or hierarchical) miss-only
    exchange -> fan-out + merge, ONE dispatch, no host syncs."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..utils.compat import shard_map

    dev = self.device_arrays()
    ax = tuple(self.mesh.axis_names)
    core = self._shard_body(b)

    def body(shard, repl, stats, ids, mask):
      # per-shard views: feat_ids [1, n], feats [1, n, F], ids [1, b]
      # feats keeps its [1, n, F] axis (see lookup_local)
      views = {k: a if k == 'feats' else a[0] for k, a in shard.items()}
      out, new_stats = core(*self.table_args(views, repl), stats[0],
                            ids[0], mask[0])
      return out[None], new_stats[None]

    fn = shard_map(
        body, mesh=self.mesh,
        in_specs=(P(ax), P(), P(ax), P(ax), P(ax)),
        out_specs=(P(ax), P(ax)))
    jfn = jax.jit(fn)
    shard = {k: dev[k] for k in self.SHARD_KEYS}
    repl = {k: dev[k] for k in self.REPL_KEYS}

    def run(ids, mask):
      out, self._stats = jfn(shard, repl, self._stats_dev(), ids, mask)
      return out

    return run

  def get(self, ids, mask=None):
    """Sharded lookup: ids [P, B] (per-shard request blocks) -> [P, B, F].

    ONE program dispatch, zero host syncs (the hit/miss counters stay on
    device — see :meth:`stats`). Reference: DistFeature.async_get /
    __getitem__ (dist_feature.py:122-153).
    """
    import jax.numpy as jnp

    from ..utils import trace
    ids = jnp.asarray(ids)
    assert ids.ndim == 2 and ids.shape[0] == self.num_partitions
    if mask is None:
      mask = ids >= 0
    b = ids.shape[1]
    if b not in self._fns:
      from ..metrics import programs
      self._fns[b] = programs.instrument(self._build_fn(b),
                                         'dist_feature.get')
    trace.record_dispatch('dist_feature.get')
    return self._fns[b](ids, mask)

  def cpu_get(self, ids) -> np.ndarray:
    """Host-side exact gather (server-side remote serving path). A
    store built by :meth:`from_device_shards` has no host rows: each
    partition's rows are gathered on its device and fetched."""
    ids = np.asarray(ids)
    out = np.zeros((ids.shape[0], self.feature_dim), self.storage_dtype)
    if self.feats is None:
      by_part = lambda a: {s.index[0].start or 0: s.data[0]
                           for s in a.addressable_shards}
      fid, rows = by_part(self._dev['feat_ids']), by_part(self._dev['feats'])
    for p in range(self.num_partitions):
      m = self.feature_pb[np.clip(ids, 0, None)] == p
      if not m.any():
        continue
      if self.feats is None:
        table = np.asarray(fid[p])
        pos = np.clip(np.searchsorted(table, ids[m]), 0,
                      table.shape[0] - 1)
        out[m] = np.asarray(rows[p][pos])
        continue
      pos = np.searchsorted(self.feat_ids[p], ids[m])
      pos = np.clip(pos, 0, self.feat_ids.shape[1] - 1)
      out[m] = self.feats[p][pos]
    return out
