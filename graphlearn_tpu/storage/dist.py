"""TieredDistFeature: per-shard out-of-core storage behind the PR 3
hot-cache / miss-exchange machinery.

``DistFeature`` keeps every partition's rows resident in host RAM (the
[P, n_max, F] block it uploads and serves ``cpu_get`` from) — at
products scale that is already ~GBs per host, and at the 100M–1B-node
production scale ROADMAP item 2 names it is impossible. This subclass
keeps only the ROUTING structures resident (the sorted [P, n_max] id
table, the partition book, the replicated hot cache) and moves the row
payload to per-partition memory-mapped disk tiers (storage/disk.py):

* ``cpu_get`` (the server-side remote serving path, cache
  construction) gathers through the mmaps — the OS page cache is the
  warm tier;
* ``device_arrays`` uploads the HBM shard table straight from disk via
  ``jax.make_array_from_callback``: each addressable shard's block is
  read transiently, so peak host RAM during upload is ONE shard's
  block, never the whole table;
* everything else — the one-dispatch cached miss-only exchange, the
  [P, 4] on-device stats, ``publish_stats`` — is inherited unchanged:
  the device-side lookup semantics are bit-identical to DistFeature
  built from the same rows (tests/test_storage.py pins it).

Device oversubscription THROUGH the shard exchange (docs/storage.md):
by default the HBM tier still holds each shard's full partition — the
exchange program must answer arbitrary remote requests in-program. With
``hot_prefix_rows=H`` set, :meth:`dist_scan_tables` uploads only the
first H positions of each partition (plus the small routing
structures), and ``storage.TieredDistScanTrainer`` answers the
remaining positions from per-chunk staged slabs computed by the epoch
prologue's exact miss-exchange program — the
``DistFeature._shard_body(slab=True)`` lookup path.

PER-STEP demand paging (PR 16): the per-step loader path
(``DistFeature.get`` on arbitrary [P, b] request blocks) rides the
SAME slab-backed lookup. An oversubscribed store overrides
``_build_fn`` so each ``get`` step routes its own miss set on the host
— ``planner.plan_exchange`` over the step's ids as a one-chunk plan,
the exact searchsorted-position routing the scanned prologue uses —
gathers those positions from the disk tiers into a pow2-padded
[P, cap] slab (``DistChunkStager._gather``'s layout), and dispatches
the ``_shard_body(slab=True)`` program over the hot prefix + slab.
Under the exact per-step plan every requested position >= H is in the
slab, so the returned rows are bit-identical to the all-HBM program
(tests/test_dist_oversub.py pins it). Per-step staging is inherently
synchronous — the request set only exists at step time — i.e. the
demand-paged path IS the ChunkStager degrade-to-sync contract applied
every step: each page counts into ``storage.prefetch_miss`` alongside
the new ``storage.demand_pages`` / ``storage.demand_paged_rows`` /
``storage.demand_page_ms`` series under a ``storage.demand_page`` span
(docs/observability.md).
"""
import os
from typing import Optional

import numpy as np

from ..distributed.dist_feature import INT32_MAX, DistFeature
from .disk import DiskTier, spill_array


class TieredDistFeature(DistFeature):
  """DistFeature whose per-partition row payloads live on disk.

  Args (beyond DistFeature's): ``spill_dir`` — where the per-partition
  tiers are written when ``feat_parts`` carries in-RAM arrays. A part's
  rows may also be given as a ``DiskTier`` directly (ids then must
  already be in sorted-id order, the layout :func:`spill_partitions`
  writes).
  """

  def __init__(self, num_partitions: int, feat_parts, feature_pb,
               mesh=None, dtype=None, spill_dir: Optional[str] = None,
               rows_per_chunk: int = 65536, fmt: str = 'npy',
               hot_prefix_rows: int = 0, **kwargs):
    self._spill_dir = spill_dir
    self._rows_per_chunk = int(rows_per_chunk)
    self._fmt = fmt
    # per-partition HBM hot prefix for the oversubscribed scanned path
    # (storage/dist_scan.py): positions [0, H) of each partition's
    # sorted row table stay device-resident; the rest stage per chunk
    self.hot_prefix_rows = int(hot_prefix_rows)
    self._scan_dev = None
    # demand-paged per-step programs, keyed b -> {slab cap -> jitted fn}
    self._slab_fns = {}
    super().__init__(num_partitions, feat_parts, feature_pb, mesh=mesh,
                     dtype=dtype, **kwargs)

  # ------------------------------------------------------------- storage

  def _init_storage(self, feat_parts, dtype):
    first_rows = feat_parts[0][1]
    f = (first_rows.dim if isinstance(first_rows, DiskTier)
         else np.asarray(first_rows).shape[1])
    dt = np.dtype(dtype) if dtype is not None else (
        first_rows.dtype if isinstance(first_rows, DiskTier)
        else np.asarray(first_rows).dtype)
    p = len(feat_parts)
    n_max = max((ids.shape[0] for ids, _ in feat_parts), default=1)
    self.n_max = int(n_max)
    self._fdim = int(f)
    self.storage_dtype = dt
    self.feat_ids = np.full((p, n_max), INT32_MAX, np.int32)
    self._tiers = []
    for i, (ids, rows) in enumerate(feat_parts):
      ids = np.asarray(ids)
      if isinstance(rows, DiskTier):
        if rows.rows != ids.shape[0]:
          raise ValueError(f'partition {i}: tier holds {rows.rows} '
                           f'rows for {ids.shape[0]} ids')
        if ids.size > 1 and np.any(np.diff(ids) < 0):
          raise ValueError(f'partition {i}: a DiskTier part must carry '
                           'rows in sorted-id order (spill_partitions '
                           'writes that layout)')
        self.feat_ids[i, :ids.shape[0]] = ids
        self._tiers.append(rows)
      else:
        if self._spill_dir is None:
          raise ValueError('TieredDistFeature needs spill_dir=... when '
                           'feat_parts carry in-RAM arrays (rows are '
                           'written as memory-mapped chunk files)')
        order = np.argsort(ids)
        rows = np.asarray(rows)
        if dtype is not None:
          rows = rows.astype(dt)
        self.feat_ids[i, :ids.shape[0]] = ids[order]
        self._tiers.append(spill_array(
            os.path.join(self._spill_dir, f'part_{i:03d}'), rows[order],
            rows_per_chunk=self._rows_per_chunk, fmt=self._fmt))

  def _part_rows(self, p: int) -> int:
    return self._tiers[p].rows

  # -------------------------------------------------------------- access

  def cpu_get(self, ids) -> np.ndarray:
    """Host-side exact gather via the per-partition mmaps — semantics
    identical to DistFeature.cpu_get over the same rows."""
    ids = np.asarray(ids)
    out = np.zeros((ids.shape[0], self.feature_dim), self.storage_dtype)
    for p in range(self.num_partitions):
      m = self.feature_pb[np.clip(ids, 0, None)] == p
      if not m.any():
        continue
      pos = np.searchsorted(self.feat_ids[p], ids[m])
      pos = np.clip(pos, 0, self.feat_ids.shape[1] - 1)
      n_p = self._part_rows(p)
      real = pos < n_p        # pad slots read as zero rows, like the
      vals = np.zeros((int(m.sum()), self.feature_dim),  # base's zero
                      self.storage_dtype)                # padding
      if real.any():
        vals[real] = self._tiers[p].gather(pos[real])
      out[m] = vals
    return out

  def device_arrays(self):
    """Upload the shard table straight from the disk tiers: each
    addressable shard's [n_max, F] block is assembled transiently in
    the make_array_from_callback callback — whole-table host RAM is
    never allocated.

    OVERSUBSCRIBED stores refuse this path: with ``hot_prefix_rows``
    set, the operator declared that a shard's full partition does NOT
    fit in HBM — uploading the full [P, n_max, F] table anyway would
    silently defeat the oversubscription, or OOM on a real topology.
    The store's OWN per-step ``get`` never comes here any more (its
    ``_build_fn`` override demand-pages through ``dist_scan_tables``,
    module docstring); this error now guards only DIRECT external
    consumers of the full table."""
    if self.hot_prefix_rows > 0:
      raise RuntimeError(
          f'TieredDistFeature(hot_prefix_rows={self.hot_prefix_rows}) '
          'is OVERSUBSCRIBED: device_arrays() would upload the full '
          f'[{self.num_partitions}, {self.n_max}, {self.feature_dim}] '
          'partition table to HBM, silently defeating the declared '
          'oversubscription (or OOMing at real scale). Per-step get() '
          'demand-pages automatically (hot prefix + per-step slab, '
          "docs/storage.md 'Demand-paged per-step gather'), and the "
          'scanned path stages per chunk via '
          'storage.TieredDistScanTrainer; a consumer that really needs '
          'the full table must construct the store with '
          'hot_prefix_rows=0 to accept the full upload')
    if self._dev is None:
      import jax
      from jax.sharding import NamedSharding, PartitionSpec as P

      shard = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
      shape = (self.num_partitions, self.n_max, self.feature_dim)

      def part_block(p: int) -> np.ndarray:
        block = np.zeros((self.n_max, self.feature_dim),
                         self.storage_dtype)
        n_p = self._part_rows(p)
        if n_p:
          block[:n_p] = self._tiers[p].gather(np.arange(n_p))
        return block

      def cb(index):
        ps = range(*index[0].indices(self.num_partitions))
        block = np.stack([part_block(p) for p in ps]) if ps else \
            np.zeros((0,) + shape[1:], self.storage_dtype)
        return block[(slice(None),) + tuple(index[1:])]

      self._dev = dict(
          feats=jax.make_array_from_callback(shape, shard, cb),
          **self._routing_arrays())
    return self._dev

  def gather_positions(self, p: int, positions: np.ndarray) -> np.ndarray:
    """Partition-``p`` rows by POSITION in its sorted row table (the
    staging pipeline's read path — positions are what the miss-exchange
    program stages and what ``_shard_body(slab=True)`` resolves)."""
    return self._tiers[p].gather(np.asarray(positions, np.int64))

  def dist_scan_tables(self):
    """Device arrays for the OVERSUBSCRIBED scanned exchange
    (storage.TieredDistScanTrainer): the [P, H, F] hot-prefix blocks —
    positions [0, H) of each partition, bit-identical to the full
    upload's leading rows — plus the small routing structures
    (sorted id table, partition book, replicated hot cache). The full
    [P, n_max, F] table is NEVER uploaded on this path; the remaining
    positions arrive per chunk as staged slabs."""
    if self._scan_dev is None:
      import jax
      from jax.sharding import NamedSharding, PartitionSpec as P

      from ..utils import global_device_put
      h = self.hot_prefix_rows
      if h < 1:
        raise ValueError(
            'dist_scan_tables needs hot_prefix_rows >= 1 (the scanned '
            'chunk program clamps pad positions into the hot prefix) — '
            'pass hot_prefix_rows=... to TieredDistFeature')
      shard = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
      hot = np.zeros((self.num_partitions, h, self.feature_dim),
                     self.storage_dtype)
      for p in range(self.num_partitions):
        n_p = min(h, self._part_rows(p))
        if n_p:
          hot[p, :n_p] = self._tiers[p].gather(np.arange(n_p))
      self._scan_dev = dict(hot=global_device_put(hot, shard),
                            **self._routing_arrays())
    return self._scan_dev

  # ---------------------------------------------- per-step demand paging

  def _demand_slab(self, ids_host: np.ndarray, mask_host: np.ndarray):
    """Host miss routing + tier gather for ONE step's [P, b] request
    block: ``planner.plan_exchange`` over the masked ids as a
    single-chunk plan (the scanned prologue's exact position routing —
    replicated-cache hits drop before routing, owners come from the
    partition book, positions from searchsorted over the sorted id
    table, positions < H are HBM-resident and drop out), then the
    staged positions gather from the disk tiers into the
    ``DistChunkStager._gather`` slab layout. Returns ``(slab_pos
    [P, cap] int32 sorted + INT32_MAX pads, slab_rows [P, cap, F],
    staged_row_count)``."""
    from . import planner
    nparts, n_max = self.num_partitions, self.n_max
    masked = np.where(mask_host, ids_host, -1)
    plan = planner.plan_exchange(
        masked, masked.shape[1], self.feature_pb, self.feat_ids,
        self.hot_prefix_rows, cache_ids=self.cache_ids)
    enc = plan.chunk_rows[0]
    cap = plan.slab_caps()[0]
    owners = enc // n_max
    pos = enc % n_max
    counts = (np.bincount(owners, minlength=nparts) if enc.size
              else np.zeros((nparts,), np.int64))
    slab_pos = np.full((nparts, cap), INT32_MAX, np.int32)
    slab_rows = np.zeros((nparts, cap, self.feature_dim),
                         self.storage_dtype)
    for p in range(nparts):
      kp = int(counts[p])
      if kp:
        m = owners == p
        slab_pos[p, :kp] = pos[m].astype(np.int32)
        slab_rows[p, :kp] = self.gather_positions(p, pos[m])
    return slab_pos, slab_rows, int(enc.shape[0])

  def _build_slab_fn(self, b: int, cap: int):
    """The slab-backed per-step lookup program, keyed (b, cap): the
    base ``_build_fn`` shard_map shape with ``_shard_body(slab=True)``
    as the core — feats is the (hot, slab_pos, slab_rows) pytree
    instead of the full [n, F] partition view."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..utils.compat import shard_map
    ax = tuple(self.mesh.axis_names)
    core = self._shard_body(b, slab=True)

    def body(shard, repl, stats, ids, mask):
      views = jax.tree.map(lambda a: a[0], shard)
      out, new_stats = core(
          *self.table_args(views, repl, (views['hot'], views['slab_pos'],
                                         views['slab_rows'])),
          stats[0], ids[0], mask[0])
      return out[None], new_stats[None]

    fn = shard_map(
        body, mesh=self.mesh,
        in_specs=(P(ax), P(), P(ax), P(ax), P(ax)),
        out_specs=(P(ax), P(ax)))
    return jax.jit(fn)

  def _build_fn(self, b: int):
    """Per-step lookup program. All-HBM stores (hot_prefix_rows == 0)
    keep DistFeature's one-dispatch program over the full partition
    table; OVERSUBSCRIBED stores get the demand-paged path (module
    docstring): per-step host miss routing + tier gather into a pow2
    slab, then the ``_shard_body(slab=True)`` program over the hot
    prefix — bit-identical rows, one extra host round trip per step."""
    if self.hot_prefix_rows <= 0:
      return super()._build_fn(b)
    import functools
    return functools.partial(self._demand_run, b)

  def _demand_run(self, b: int, ids, mask):
    """One demand-paged per-step dispatch: host miss routing + tier
    gather into the step's slab, sharded upload, and the (b, cap)
    slab-backed program. Host-side by design — the per-step request
    set only exists at step time, so the page is the explicit host
    round trip the ChunkStager's degrade-to-sync path makes at a chunk
    boundary, taken every step."""
    import time as _time

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import metrics
    from ..metrics import spans
    from ..utils import global_device_put
    scan = self.dist_scan_tables()
    # explicit fetch — the strict guards reject implicit transfers only
    ids_host = np.asarray(jax.device_get(ids))
    mask_host = np.asarray(jax.device_get(mask))
    with spans.span('storage.demand_page', b=int(ids_host.shape[1])):
      t0 = _time.perf_counter()
      slab_pos_np, slab_rows_np, staged = self._demand_slab(
          ids_host, mask_host)
      metrics.observe('storage.demand_page_ms',
                      (_time.perf_counter() - t0) * 1e3)
      metrics.inc('storage.demand_pages')
      if staged:
        metrics.inc('storage.demand_paged_rows', staged)
        # every demand page is, definitionally, a prefetch miss: the
        # sync-stage counter keeps the degrade-to-sync accounting
        # comparable across the scanned and per-step paths
        metrics.inc('storage.prefetch_miss', staged)
    sharded = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
    slab_pos = global_device_put(slab_pos_np, sharded)
    slab_rows = global_device_put(slab_rows_np, sharded)
    cap = int(slab_pos_np.shape[1])
    fns = self._slab_fns.setdefault(b, {})
    jfn = fns.get(cap)
    if jfn is None:
      jfn = fns[cap] = self._build_slab_fn(b, cap)
    out, self._stats = jfn(
        dict(feat_ids=scan['feat_ids'], feat_starts=scan['feat_starts'],
             hot=scan['hot'], slab_pos=slab_pos, slab_rows=slab_rows),
        {k: scan[k] for k in self.REPL_KEYS}, self._stats_dev(), ids, mask)
    return out

  def tier_bytes(self) -> dict:
    """Resident vs on-disk byte accounting (sizing guidance,
    docs/storage.md)."""
    disk = sum(t.nbytes for t in self._tiers)
    resident = self.feat_ids.nbytes + self.feature_pb.nbytes
    if self.cache_feats is not None:
      resident += self.cache_feats.nbytes + self.cache_ids.nbytes
    return dict(disk_bytes=int(disk), resident_bytes=int(resident))


def spill_partitions(spill_dir: str, feat_parts, rows_per_chunk: int =
                     65536, fmt: str = 'npy'):
  """Write per-partition (ids, rows) blocks as sorted-id disk tiers and
  return ``[(sorted_ids, DiskTier), ...]`` — the layout
  TieredDistFeature consumes directly (and the offline step a
  partitioner can run once so later runs never touch the raw arrays)."""
  out = []
  for i, (ids, rows) in enumerate(feat_parts):
    ids = np.asarray(ids)
    order = np.argsort(ids)
    tier = spill_array(os.path.join(spill_dir, f'part_{i:03d}'),
                       np.asarray(rows)[order],
                       rows_per_chunk=rows_per_chunk, fmt=fmt)
    out.append((ids[order], tier))
  return out
