"""Unified host/device feature table.

TPU-native re-design of the reference's UnifiedTensor
(/root/reference/graphlearn_torch/csrc/cuda/unified_tensor.cu and
python/data/unified_tensor.py): there, a virtual 2-D tensor spans shards on
several p2p GPUs plus a pinned-CPU zero-copy shard, and a warp-per-row gather
kernel resolves the owning device by binary search over an offset table.

On TPU there is no UVA: device reads cannot page host memory. The equivalent
split is *hot rows resident in HBM* (optionally sharded over a device group —
XLA's gather resolves the shard, replacing the reference's device binary
search) and *cold rows in host RAM*. The mixed gather ships ONLY cold rows
across the bus (the whole point of the reference's split: only misses touch
the UVA path, unified_tensor.cu:48-81):

  1. the cold subset is computed on host and gathered there — in a worker
     thread, overlapping the device-side hot gather's async dispatch;
  2. the cold block is padded to a power-of-two row count (bounds the number
     of distinct compiled scatter shapes) and shipped once;
  3. a jitted scatter drops the cold rows into their batch positions.

Transfer per batch is O(miss_count * F), not O(B * F).
"""
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from ..utils.trace import record_dispatch


def _next_pow2(n: int) -> int:
  return 1 << max(0, (n - 1).bit_length())


class UnifiedTensor:
  """A virtual [N, F] tensor = device part (rows [0, H)) + host part [H, N).

  Reference parity: UnifiedTensor::InitFrom / AppendCPUTensor /
  AppendSharedTensor / operator[] (unified_tensor.cu:168-338). The device
  part plays the role of the GPU shards; the host part replaces the
  pinned-CPU zero-copy shard.

  ``device`` may be a jax.Device or a jax.sharding.Sharding — the latter
  row-shards the hot block over a device group (reference DeviceGroup
  placement, unified_tensor.cu:233-269).
  """

  def __init__(self, device=None, dtype=None):
    self.device = device
    self.dtype = dtype
    self._device_part = None   # jax.Array [H, F] in HBM
    self._host_part = None     # np.ndarray [N-H, F] in host RAM
    self._device_rows = 0
    self._host_rows_n = 0      # virtual host-row count (tiers may stack)
    self._pool = None          # lazy host-gather worker
    self._hot_fn = None        # jitted hot gather (dispatched pre-block)
    self._scatter_fn = None    # jitted cold-row scatter
    self._last_cold_cap = None  # introspection for tests/benchmarks

  def init_from(self, device_rows: Optional[np.ndarray],
                host_rows: Optional[np.ndarray]):
    """Build from a hot (device) block and a cold (host) block.

    Reference: UnifiedTensor::InitFrom(tensors, devices) +
    AppendCPUTensor (unified_tensor.cu:202,271).
    """
    import jax
    if device_rows is not None and device_rows.size:
      arr = np.ascontiguousarray(device_rows)
      if self.dtype is not None:
        arr = arr.astype(self.dtype)
      self._device_part = (jax.device_put(arr, self.device)
                           if self.device is not None else jax.device_put(arr))
      self._device_rows = int(arr.shape[0])
    if host_rows is not None and host_rows.size:
      arr = np.ascontiguousarray(host_rows)
      if self.dtype is not None:
        arr = arr.astype(self.dtype)
      self._host_part = arr
      self._host_rows_n = int(arr.shape[0])
    return self

  @property
  def device_part(self):
    return self._device_part

  @property
  def host_part(self):
    return self._host_part

  @property
  def host_rows(self) -> int:
    """Rows resolved on the host side (everything past the device
    prefix). Subclasses stacking deeper tiers (storage.TieredFeature's
    warm-RAM + disk tensor) report their combined span here."""
    return self._host_rows_n

  def _host_resolve(self, rel_ids: np.ndarray) -> np.ndarray:
    """Host rows for host-relative indices [0, host_rows) — THE staging
    hook: the base class reads its resident host block; the tiered
    tensor (storage/tiered.py) overrides this to resolve warm-RAM rows,
    the staging ring, and memory-mapped disk chunks."""
    return np.take(self._host_part, rel_ids, axis=0)

  @property
  def shape(self):
    h = self._device_rows
    n = h + self._host_rows_n
    f = (self._device_part.shape[1] if self._device_part is not None
         else self._host_part.shape[1])
    return (n, f)

  @property
  def size(self) -> int:
    return self.shape[0]

  def _fns(self):
    """(hot gather, cold scatter) jitted fns — jit's own shape-keyed cache
    handles distinct (B, cold_cap) combinations."""
    import jax
    import jax.numpy as jnp
    if self._hot_fn is None:
      self._hot_fn = jax.jit(
          lambda table, hot_ids: jnp.take(table, hot_ids, axis=0))
      # positions beyond the cold count are padded to b -> dropped
      self._scatter_fn = jax.jit(
          lambda out, pos, rows: out.at[pos].set(rows, mode='drop'))
    return self._hot_fn, self._scatter_fn

  def __getitem__(self, ids):
    """Gather rows by global row index; returns a device array.

    Hot rows come straight from HBM; ONLY cold rows cross the bus, padded
    to a power-of-two count (bounded recompiles). The hot gather is
    dispatched (async) BEFORE blocking on the worker-thread host gather,
    so the device works while the host collects the misses. Cold ids
    require host knowledge of ``ids`` — callers on the all-hot path
    (Feature.device_table) never reach this.
    """
    import jax
    import jax.numpy as jnp
    if self._host_rows_n == 0:
      if self._pallas_ok():
        from ..ops import gather_rows_hbm2
        return gather_rows_hbm2(self._device_part, jnp.asarray(ids))
      return jnp.take(self._device_part, jnp.asarray(ids), axis=0)
    ids_np = np.asarray(ids)
    if self._device_part is None:
      host = self._host_resolve(ids_np - self._device_rows)
      return jax.device_put(host, self._small_block_target())
    # Mixed: ship only the cold rows.
    b = ids_np.shape[0]
    is_hot = ids_np < self._device_rows
    cold_pos = np.nonzero(~is_hot)[0]
    n_cold = int(cold_pos.shape[0])
    cold_cap = min(b, max(1, _next_pow2(n_cold)))
    self._last_cold_cap = cold_cap
    if self._pool is None:
      self._pool = ThreadPoolExecutor(max_workers=1)

    def host_gather():
      rows = self._host_resolve(ids_np[cold_pos] - self._device_rows)
      if n_cold < cold_cap:
        pad = np.zeros((cold_cap - n_cold,) + rows.shape[1:], rows.dtype)
        rows = np.concatenate([rows, pad]) if n_cold else pad
      return rows

    fut = self._pool.submit(host_gather)
    hot_fn, scatter_fn = self._fns()
    hot_ids = jnp.asarray(np.where(is_hot, ids_np, 0))
    record_dispatch('unified_tensor.hot_gather')
    out = hot_fn(self._device_part, hot_ids)   # async; overlaps host work
    pos = np.full((cold_cap,), b, np.int32)    # pad positions drop
    pos[:n_cold] = cold_pos
    cold_rows = jax.device_put(fut.result(), self._small_block_target())
    record_dispatch('unified_tensor.cold_scatter')
    return scatter_fn(out, jnp.asarray(pos), cold_rows)

  use_pallas_v2 = False   # opt-in: the run-segmented multi-row DMA
  # gather (ops.gather_rows_hbm2), not yet timed on the chip (ROADMAP.md
  # S6): auto-route only once a cell shows a win

  def _pallas_ok(self) -> bool:
    """All-hot gathers use the Pallas row-DMA kernel when opted in.
    Off-TPU the flag is inert; on TPU a table the kernel cannot serve
    raises (ops.table_kernel_ok)."""
    if not self.use_pallas_v2 or self._device_part is None:
      return False
    from ..ops.gather_pallas import table_kernel_ok
    return table_kernel_ok('UnifiedTensor', self._device_part)

  def _small_block_target(self):
    """Placement for per-batch blocks: replicated when the hot table is
    group-sharded (a cold block's row count need not divide the group)."""
    import jax
    if isinstance(self.device, jax.sharding.Sharding):
      from jax.sharding import NamedSharding, PartitionSpec as P
      return NamedSharding(self.device.mesh, P())
    return self.device

  def share_ipc(self):
    """Single-process-per-host on TPU: sharing = handing over host arrays
    (reference ShareCUDAIpc, unified_tensor.cu:367-381)."""
    dev = (np.asarray(self._device_part)
           if self._device_part is not None else None)
    return dev, self._host_part, self.device
