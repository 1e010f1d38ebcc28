"""Feature store: hot rows in HBM, cold rows on host.

TPU-native re-design of /root/reference/graphlearn_torch/python/data/feature.py.
The reference splits rows by ``split_ratio`` into a GPU part (replicated per
NVLink ``DeviceGroup``, sharded within the group via UnifiedTensor p2p) and a
pinned-CPU zero-copy part. On TPU the split maps to: hot prefix resident in
device HBM (optionally sharded across a mesh axis — replication/sharding is
XLA's job, so ``DeviceGroup`` is a thin shard-placement descriptor), cold tail
in host RAM gathered per batch. ``id2index`` carries the hotness reorder
(data/reorder.py) exactly like the reference (feature.py:147-153).
"""
from typing import List, Optional, Sequence

import numpy as np

from .unified_tensor import UnifiedTensor


class DeviceGroup:
  """A group of devices that jointly hold one replica of the hot rows.

  Reference: data/feature.py:31-44 (NVLink p2p groups: the hot table is
  sharded across the group's GPUs and gathered via p2p pointers,
  unified_tensor.cu:233-269). On TPU the group becomes a row-sharding of
  the hot block over the group's devices — ``sharding()`` builds the
  1-axis mesh placement and XLA's gather resolves the owning shard
  (collectives over ICI) instead of p2p pointer chasing.
  """

  def __init__(self, group_id: int, device_list: Sequence):
    self.group_id = group_id
    self.device_list = list(device_list)

  @property
  def size(self):
    return len(self.device_list)

  def sharding(self):
    """NamedSharding that row-shards a [H, F] table over this group."""
    import numpy as np_
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np_.array(self.device_list), ('f',))
    return NamedSharding(mesh, P('f'))


class Feature:
  """2-D feature store with hot/cold split (reference: data/feature.py:47-279).

  Args:
    feature_array: [N, F] host rows (already reordered if ``id2index`` given).
    split_ratio: fraction of rows kept in HBM (0 = all host, 1 = all HBM).
    device_group_list: optional DeviceGroups for sharded HBM placement.
    device: explicit device for the hot part (default: default device).
    with_device: False forces a pure-host store (reference ``with_gpu``).
    id2index: optional [N] old-id -> row map from the hotness reorder.
    dtype: optional storage dtype (e.g. jnp.bfloat16 to halve HBM).
    cache_rows: absolute HBM row count (overrides ``split_ratio``; the
      same knob pair as the distributed store, DistFeature).
  """

  def __init__(
      self,
      feature_array: np.ndarray,
      split_ratio: float = 0.0,
      device_group_list: Optional[List[DeviceGroup]] = None,
      device=None,
      with_device: bool = True,
      id2index: Optional[np.ndarray] = None,
      dtype=None,
      cache_rows: Optional[int] = None,
  ):
    self.feature_array = np.asarray(feature_array)
    n = self.feature_array.shape[0]
    self.cache_rows = (min(max(int(cache_rows), 0), n)
                       if cache_rows is not None else None)
    if self.cache_rows is not None and n:
      split_ratio = self.cache_rows / n
    self.split_ratio = float(split_ratio)
    self.device_group_list = device_group_list
    self.device = device
    self.with_device = with_device
    self._id2index = id2index
    self.dtype = dtype
    self._unified = None
    self._id2index_dev = None

  def lazy_init(self):
    if self._unified is not None:
      return
    n = self.feature_array.shape[0]
    if not self.with_device:
      hot = 0
    elif self.cache_rows is not None:
      hot = self.cache_rows
    else:
      hot = int(n * self.split_ratio)
    place = self.device
    hot_block = self.feature_array[:hot] if hot else None
    if self.device_group_list:
      # shard the hot block over the (first) device group; further groups
      # are replicas, which multi-host placement handles upstream
      # (reference: one replica per NVLink group, feature.py:177-205)
      group = self.device_group_list[0]
      if group.size > 1:
        place = group.sharding()
        rem = hot % group.size
        if rem and hot == n:
          # full-HBM split: pad UP with masked rows so no tail strands on
          # host (which would disable the fused device_table path)
          pad = np.zeros((group.size - rem,) + self.feature_array.shape[1:],
                         self.feature_array.dtype)
          hot_block = np.concatenate([self.feature_array, pad])
          hot += group.size - rem
        elif rem:
          # mixed split: round DOWN (the few demoted rows stay cold)
          hot -= rem
          hot_block = self.feature_array[:hot] if hot else None
      elif group.device_list:
        place = group.device_list[0]
    ut = UnifiedTensor(device=place, dtype=self.dtype)
    ut.init_from(hot_block,
                 self.feature_array[hot:] if hot < n else None)
    self._unified = ut
    if self._id2index is not None:
      import jax
      self._id2index_dev = jax.device_put(self._id2index, self.device)

  @property
  def id2index(self):
    return self._id2index

  @property
  def unified(self) -> UnifiedTensor:
    self.lazy_init()
    return self._unified

  def __getitem__(self, ids):
    """Gather rows for global node ids (applies id2index remap).

    Reference: Feature.__getitem__ (feature.py:140-153).
    """
    import jax.numpy as jnp
    self.lazy_init()
    # FILL(-1) pad slots must not cost a host-row fetch: jnp.take would
    # WRAP -1 to the last row (cold tail after a degree reorder). Clamp
    # pads to STORAGE row 0 — after the remap, so it is the hottest row
    # by construction — not to node id 0, whose remapped row can be cold.
    # Rows for pad slots are masked downstream; any value serves.
    ids = jnp.asarray(ids)
    pad = ids < 0
    idx = jnp.maximum(ids, 0)
    if self._id2index_dev is not None:
      idx = jnp.take(self._id2index_dev, idx, axis=0)
    idx = jnp.where(pad, 0, idx)
    return self._unified[idx]

  def device_table(self):
    """(feats_dev, id2index_dev) when ALL rows are HBM-resident, else None.

    Loaders use this to fuse the feature gather into a single jitted
    collate dispatch (ops.collate); with a host (cold) part the gather
    goes through ``__getitem__``'s mixed path instead.
    """
    self.lazy_init()
    if self._unified.host_rows or self._unified.device_part is None:
      return None
    return self._unified.device_part, self._id2index_dev

  def cpu_get(self, ids) -> np.ndarray:
    """Pure-host gather (used by remote feature serving where the result is
    immediately serialized; reference Feature.cpu_get via feature.py:122-132
    local_get path)."""
    ids = np.asarray(ids)
    if self._id2index is not None:
      ids = self._id2index[ids]
    return self.feature_array[ids]

  @property
  def shape(self):
    return self.feature_array.shape

  @property
  def size(self) -> int:
    return int(self.feature_array.shape[0])

  def __len__(self):
    return self.size

  def share_ipc(self):
    """Hand host arrays to another consumer (reference feature.py:240-257's
    CUDA-IPC re-init collapses to host-array handoff on TPU)."""
    return (self.feature_array, self.split_ratio, self.device,
            self.with_device, self._id2index, self.dtype,
            self.cache_rows)

  @classmethod
  def from_ipc_handle(cls, handle):
    arr, split_ratio, device, with_device, id2index, dtype, *rest = handle
    cache_rows = rest[0] if rest else None
    return cls(arr, split_ratio, None, device, with_device, id2index,
               dtype, cache_rows=cache_rows)
