"""TieredFeature: the three-tier HBM -> host-RAM -> disk feature store.

GLT's UnifiedTensor spans GPU HBM plus a pinned-CPU zero-copy shard so
only misses cross the bus (PAPER.md, unified_tensor.cu); our two-tier
``data.Feature`` port still required every row in host RAM. This store
adds the third tier: storage rows ``[0, H)`` are HBM-resident (the hot
prefix, after the hotness reorder), ``[H, H+W)`` live in host RAM (the
warm tier), and ``[H+W, N)`` live on disk as memory-mapped chunk files
(storage/disk.py) — a products-scale (2.45M-node) or papers-scale
feature table fits on a machine whose RAM holds only the warm prefix.

``TieredFeature`` plugs in wherever ``data.Feature`` is accepted (the
loaders' mixed-gather path, ``cpu_get`` serving, ``Dataset`` stores):
it subclasses Feature and routes host-row resolution through
``UnifiedTensor._host_resolve`` — warm rows read RAM, cold rows first
consult the staging ring of promoted blocks (rows the chunk-boundary
prefetcher, storage/staging.py, already pulled), then fall back to a
synchronous mmap gather counted in ``storage.prefetch_miss``.
Synchronously-read cold rows are promoted into a bounded warm cache so
reactive (per-batch) workloads self-warm.

The scanned-epoch integration — where the epoch's whole miss set is
planned up front and staged ahead of each chunk — lives in
storage/scan.py (``TieredScanTrainer``).
"""
import threading
from typing import List, Optional, Tuple

import numpy as np

from .. import metrics
from ..data.feature import Feature
from ..data.unified_tensor import UnifiedTensor
from .disk import DiskTier, spill_array


class _PromotedCache:
  """Bounded FIFO of promoted cold-row blocks, searched newest-first by
  sorted absolute storage row — the reactive half of the warm tier
  (the planned half is the staging ring, storage/staging.py)."""

  def __init__(self, capacity_rows: int):
    self.capacity_rows = int(capacity_rows)
    self._blocks: List[Tuple[np.ndarray, np.ndarray]] = []
    self._rows = 0
    self._lock = threading.Lock()

  def put(self, abs_rows_sorted: np.ndarray, rows: np.ndarray):
    if self.capacity_rows <= 0 or abs_rows_sorted.size == 0:
      return
    with self._lock:
      self._blocks.append((abs_rows_sorted, rows))
      self._rows += int(abs_rows_sorted.shape[0])
      while self._rows > self.capacity_rows and len(self._blocks) > 1:
        old_ids, _ = self._blocks.pop(0)
        self._rows -= int(old_ids.shape[0])

  def lookup(self, abs_rows: np.ndarray, out: np.ndarray,
             missing: np.ndarray) -> np.ndarray:
    """Fill ``out`` rows found in the cache; returns the updated
    ``missing`` bool mask (True = still unresolved)."""
    with self._lock:
      blocks = list(self._blocks)
    for ids, rows in reversed(blocks):
      if not missing.any():
        break
      pos = np.searchsorted(ids, abs_rows)
      pos = np.clip(pos, 0, ids.shape[0] - 1)
      hit = missing & (ids[pos] == abs_rows)
      if hit.any():
        out[hit] = rows[pos[hit]]
        missing = missing & ~hit
    return missing

  @property
  def rows(self) -> int:
    with self._lock:
      return self._rows


class _TieredTensor(UnifiedTensor):
  """UnifiedTensor whose host span stacks a warm-RAM block over a disk
  tier. The device part and the pow2 cold-block shipping machinery are
  inherited unchanged — only ``_host_resolve`` learns tiers."""

  def __init__(self, warm: Optional[np.ndarray], disk: Optional[DiskTier],
               disk_base: int, promoted: _PromotedCache,
               device=None, dtype=None):
    super().__init__(device=device, dtype=dtype)
    self._warm = warm
    self._disk = disk
    # tier-relative offset of host row (H+W) inside the DiskTier: 0 when
    # the tier holds only the cold tail, H+W when it holds all N rows
    self._disk_base = int(disk_base)
    self._promoted = promoted
    warm_n = int(warm.shape[0]) if warm is not None else 0
    disk_n = int(disk.rows - disk_base) if disk is not None else 0
    self._warm_n = warm_n
    self._host_rows_n = warm_n + disk_n

  @property
  def host_part(self):
    # the warm block is the RAM-resident host part; disk rows resolve
    # through _host_resolve (consumers must use host_rows for spans)
    return self._warm

  def _host_resolve(self, rel_ids: np.ndarray) -> np.ndarray:
    rel_ids = np.asarray(rel_ids, np.int64).reshape(-1)
    dim = (self._warm.shape[1] if self._warm is not None
           else self._disk.dim)
    dt = (self._warm.dtype if self._warm is not None else self._disk.dtype)
    out = np.zeros((rel_ids.shape[0], dim), dt)
    is_warm = rel_ids < self._warm_n
    if is_warm.any():
      out[is_warm] = self._warm[rel_ids[is_warm]]
    cold = ~is_warm
    if cold.any():
      # absolute storage rows key the promoted cache (the staging ring
      # promotes by storage row, which callers everywhere share)
      abs_rows = rel_ids[cold] + self._device_rows
      block = np.zeros((int(cold.sum()), dim), dt)
      missing = np.ones((block.shape[0],), bool)
      missing = self._promoted.lookup(abs_rows, block, missing)
      if missing.any():
        n_miss = int(missing.sum())
        metrics.inc('storage.prefetch_miss', n_miss)
        disk_rel = (rel_ids[cold][missing] - self._warm_n
                    + self._disk_base)
        read = self._disk.gather(disk_rel)
        block[missing] = read
        # promote: repeated reactive access to the same cold rows warms
        order = np.argsort(abs_rows[missing], kind='stable')
        self._promoted.put(abs_rows[missing][order], read[order])
      out[cold] = block
    return out


class TieredFeature(Feature):
  """Three-tier drop-in for ``data.Feature``.

  Args:
    source: the full [N, F] table — an in-RAM np.ndarray (its cold tail
      is spilled to ``spill_dir``), OR a ``DiskTier`` holding all N
      rows (the already-on-disk case: hot/warm prefixes are read from
      it once at init), OR a path to such a tier.
    hot_rows: H — rows [0, H) resident in HBM.
    warm_rows: W — rows [H, H+W) resident in host RAM. None with an
      array source means "everything not hot stays warm" (no disk
      tier); None with a disk source means W = 0.
    id2index: optional [N] node-id -> storage-row map from the hotness
      reorder, exactly as ``data.Feature`` (row 0 = hottest).
    dtype: optional storage dtype for the HBM tier.
    device: explicit device for the hot tier.
    spill_dir: where to write the cold tail when ``source`` is an
      array and cold rows exist (required in that case).
    rows_per_chunk / fmt: DiskTier layout knobs for the spill.
    promoted_rows: capacity of the bounded promoted-row cache reactive
      cold reads warm into (0 disables promotion).
  """

  def __init__(self, source, hot_rows: int = 0,
               warm_rows: Optional[int] = None,
               id2index: Optional[np.ndarray] = None, dtype=None,
               device=None, spill_dir: Optional[str] = None,
               rows_per_chunk: int = 65536, fmt: str = 'npy',
               promoted_rows: int = 65536):
    if isinstance(source, str):
      source = DiskTier(source)
    self._disk: Optional[DiskTier] = None
    self._warm_np: Optional[np.ndarray] = None
    self._hot_np: Optional[np.ndarray] = None
    if isinstance(source, DiskTier):
      n = source.rows
      self.hot_rows = max(0, min(int(hot_rows), n))
      w = 0 if warm_rows is None else int(warm_rows)
      self.warm_rows = max(0, min(w, n - self.hot_rows))
      self._disk = source
      self._disk_base = self.hot_rows + self.warm_rows
      if self.hot_rows:
        self._hot_np = source.gather(np.arange(self.hot_rows))
      if self.warm_rows:
        self._warm_np = source.gather(
            np.arange(self.hot_rows, self._disk_base))
      self._n, self._f = n, source.dim
      self._np_dtype = source.dtype
    else:
      arr = np.asarray(source)
      n = arr.shape[0]
      self.hot_rows = max(0, min(int(hot_rows), n))
      w = (n - self.hot_rows) if warm_rows is None else int(warm_rows)
      self.warm_rows = max(0, min(w, n - self.hot_rows))
      cold = n - self.hot_rows - self.warm_rows
      # COPIES, not views: a slice view pins the whole source array
      # (its .base) in host RAM for the store's lifetime — the caller
      # must be able to `del arr` after construction and keep only
      # hot+warm resident, or the out-of-core point is lost
      self._hot_np = (arr[:self.hot_rows].copy() if self.hot_rows
                      else None)
      self._warm_np = (arr[self.hot_rows:self.hot_rows + self.warm_rows]
                       .copy() if self.warm_rows else None)
      if cold:
        if spill_dir is None:
          raise ValueError(
              f'{cold} rows fall in the disk tier but no spill_dir was '
              'given — pass spill_dir=... (the cold tail is written as '
              'memory-mapped chunk files), or widen hot/warm to cover '
              'the table')
        self._disk = spill_array(spill_dir,
                                 arr[self.hot_rows + self.warm_rows:],
                                 rows_per_chunk=rows_per_chunk, fmt=fmt)
        self._disk_base = 0
      else:
        self._disk_base = 0
      self._n, self._f = n, int(arr.shape[1])
      self._np_dtype = arr.dtype
    self._init_surface(id2index, dtype, device, promoted_rows)

  def _init_surface(self, id2index, dtype, device, promoted_rows,
                    hot_dev=None):
    """The Feature surface over tiers already in place (no
    super().__init__: the base stores the full array; the whole point
    here is NOT holding one). ``hot_dev``: the hot prefix where it lives
    only on the device (``from_tiers``)."""
    self._hot_dev = hot_dev
    self.disk_rows = self._n - self.hot_rows - self.warm_rows
    self.split_ratio = self.hot_rows / self._n if self._n else 0.0
    self.cache_rows = self.hot_rows
    self.device_group_list = None
    self.device = device
    self.with_device = self.hot_rows > 0
    self._id2index = (np.asarray(id2index) if id2index is not None
                      else None)
    self.dtype = dtype
    self._unified = None
    self._id2index_dev = None
    self._promoted = _PromotedCache(promoted_rows)

  @classmethod
  def from_tiers(cls, hot, warm: Optional[np.ndarray],
                 id2index: Optional[np.ndarray] = None, device=None,
                 promoted_rows: int = 65536):
    """A store over tiers that are ALREADY where they belong: ``hot``
    the [H, F] hot prefix as a DEVICE array (made or filled on the
    chip), ``warm`` the [W, F] rest as ONE host array — adopted, not
    copied, so a table that does not fit the chip costs the host one
    table, where the array constructor holds the source, a copy of
    each tier and the hot prefix a second time. No disk tier (hand a
    ``DiskTier`` to the constructor for one). ``cpu_get`` and
    ``share_ipc`` fetch hot rows from the device when asked."""
    if hot is None or hot.shape[0] < 1:
      raise ValueError('from_tiers takes the hot prefix as a device '
                       'array of at least one row (scan_tables clamps '
                       'pad slots into it)')
    self = cls.__new__(cls)
    self._disk, self._disk_base = None, 0
    self._hot_np, self._warm_np = None, warm
    self.hot_rows = int(hot.shape[0])
    self.warm_rows = int(warm.shape[0]) if warm is not None else 0
    self._n = self.hot_rows + self.warm_rows
    self._f, self._np_dtype = int(hot.shape[1]), np.dtype(hot.dtype)
    if warm is not None and (warm.shape[1] != self._f or
                             warm.dtype != self._np_dtype):
      raise ValueError(f'from_tiers: warm is {warm.shape[1:]} '
                       f'{warm.dtype}, hot {hot.shape[1:]} {hot.dtype}')
    self._init_surface(id2index, None, device, promoted_rows, hot_dev=hot)
    return self

  # ------------------------------------------------------------ lifecycle

  def lazy_init(self):
    if self._unified is not None:
      return
    ut = _TieredTensor(self._warm_np, self._disk, self._disk_base,
                       self._promoted, device=self.device,
                       dtype=self.dtype)
    if self._hot_dev is not None:
      # from_tiers: the prefix is on the device already, nothing to put
      ut._device_part, ut._device_rows = self._hot_dev, self.hot_rows
    else:
      ut.init_from(self._hot_np, None)
    # init_from only sees the hot block; stamp the tiered host span
    ut._host_rows_n = self.warm_rows + self.disk_rows
    self._unified = ut
    if self._id2index is not None:
      import jax
      self._id2index_dev = jax.device_put(self._id2index, self.device)
    metrics.set_gauge('storage.hot_rows', self.hot_rows)
    metrics.set_gauge('storage.warm_rows', self.warm_rows)
    metrics.set_gauge('storage.disk_rows', self.disk_rows)

  # ------------------------------------------------------- Feature surface

  @property
  def feature_array(self):
    raise AttributeError(
        'TieredFeature holds no resident full table — use cpu_get / '
        '__getitem__ (tiers resolve per request), or stage_gather for '
        'planned blocks')

  @property
  def shape(self):
    return (self._n, self._f)

  @property
  def size(self) -> int:
    return self._n

  def cpu_get(self, ids) -> np.ndarray:
    """Pure-host gather across all three tiers (hot rows come from the
    host copy kept for IPC/rebuild; a ``from_tiers`` store keeps none
    and fetches the asked-for hot rows from HBM)."""
    ids = np.asarray(ids).reshape(-1)
    if self._id2index is not None:
      rows = self._id2index[ids]
    else:
      rows = ids
    return self._rows_host(np.asarray(rows, np.int64))

  def _rows_host(self, rows: np.ndarray) -> np.ndarray:
    out = np.zeros((rows.shape[0], self._f), self._np_dtype)
    is_hot = rows < self.hot_rows
    if is_hot.any():
      out[is_hot] = self._hot_host(rows[is_hot])
    rest = ~is_hot
    if rest.any():
      self.lazy_init()
      out[rest] = self._unified._host_resolve(rows[rest] - self.hot_rows)
    return out

  def _hot_host(self, rows: Optional[np.ndarray] = None) -> np.ndarray:
    """Hot rows on the host: all of them (``rows`` None) or a gather.
    A store whose prefix lives only on the device fetches them."""
    if self._hot_dev is None:
      return self._hot_np if rows is None else self._hot_np[rows]
    import jax
    if rows is None:
      return np.asarray(jax.device_get(self._hot_dev))
    idx = jax.device_put(np.asarray(rows, np.int32))
    return np.asarray(jax.device_get(self._hot_dev[idx]))

  def stage_gather(self, abs_rows: np.ndarray,
                   out: Optional[np.ndarray] = None) -> np.ndarray:
    """Warm/disk rows for ABSOLUTE storage rows >= hot_rows, straight
    from the tiers (no promoted-cache consult, no miss accounting) —
    the staging worker's read path (storage/staging.py). ``out``
    ([len(abs_rows), F], the store's dtype) is written in place: the
    stager hands in the head of its padded slab, so a chunk's rows are
    copied once, not gathered and then padded."""
    abs_rows = np.asarray(abs_rows, np.int64).reshape(-1)
    if abs_rows.size and abs_rows.min() < self.hot_rows:
      raise IndexError('stage_gather serves the host tiers: rows must '
                       f'be >= hot_rows ({self.hot_rows})')
    if out is None:
      out = np.empty((abs_rows.shape[0], self._f), self._np_dtype)
    rel = abs_rows - self.hot_rows
    if self.disk_rows == 0:
      # every host row is warm: one gather straight into the slab
      # (mode='clip' writes through ``out`` with no bounce buffer; the
      # ids are in range by the check above and the plan's construction)
      if rel.size:
        np.take(self._warm_np, rel, axis=0, out=out, mode='clip')
      return out
    is_warm = rel < self.warm_rows
    if is_warm.any():
      out[is_warm] = self._warm_np[rel[is_warm]]
    cold = ~is_warm
    if cold.any():
      out[cold] = self._disk.gather(rel[cold] - self.warm_rows
                                    + self._disk_base)
    return out

  def promote(self, abs_rows_sorted: np.ndarray, rows: np.ndarray):
    """Install already-gathered cold rows into the promoted cache (the
    staging pipeline's hand-off into the reactive warm path)."""
    self._promoted.put(np.asarray(abs_rows_sorted, np.int64),
                       np.asarray(rows))

  def scan_tables(self):
    """(hot_table_dev [H, F], id2index_dev) — the device-resident
    prefix the tiered scanned trainer (storage/scan.py) gathers hot
    rows from. Requires hot_rows >= 1 (pad slots clamp into the hot
    prefix)."""
    self.lazy_init()
    if self._unified.device_part is None:
      raise ValueError('TieredFeature.scan_tables needs hot_rows >= 1 '
                       '(the scanned chunk program clamps pad slots '
                       'into the hot prefix)')
    return self._unified.device_part, self._id2index_dev

  def tier_occupancy(self) -> dict:
    """Row counts per tier plus the promoted-cache fill — the
    ``storage.*`` gauge payload."""
    return dict(hot=self.hot_rows, warm=self.warm_rows,
                disk=self.disk_rows, promoted=self._promoted.rows)

  # ----------------------------------------------------------------- IPC

  def share_ipc(self):
    """Hand the tier layout to another consumer: the disk tier travels
    as its directory path (mmaps reopen on the other side), hot/warm
    blocks as host arrays (reference feature.py:240-257 — CUDA-IPC
    re-init collapses to host-array handoff on TPU)."""
    return ('tiered', self._disk.dir if self._disk is not None else None,
            self._disk_base, self._hot_host() if self.hot_rows else None,
            self._warm_np, self._id2index, self.dtype)

  @classmethod
  def from_ipc_handle(cls, handle):
    tag, disk_dir, disk_base, hot_np, warm_np, id2index, dtype = handle
    assert tag == 'tiered', tag
    obj = cls.__new__(cls)
    obj._disk = DiskTier(disk_dir) if disk_dir is not None else None
    obj._disk_base = int(disk_base)
    obj._hot_np, obj._warm_np = hot_np, warm_np
    obj.hot_rows = int(hot_np.shape[0]) if hot_np is not None else 0
    obj.warm_rows = int(warm_np.shape[0]) if warm_np is not None else 0
    disk_rows = (int(obj._disk.rows - disk_base)
                 if obj._disk is not None else 0)
    obj._n = obj.hot_rows + obj.warm_rows + disk_rows
    ref = hot_np if hot_np is not None else warm_np
    obj._f = (int(ref.shape[1]) if ref is not None else obj._disk.dim)
    obj._np_dtype = (ref.dtype if ref is not None else obj._disk.dtype)
    obj._init_surface(id2index, dtype, None, 65536)
    return obj
