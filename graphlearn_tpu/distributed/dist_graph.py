"""Sharded distributed graph: per-partition local CSRs stacked over the mesh.

TPU-native re-design of
/root/reference/graphlearn_torch/python/distributed/dist_graph.py. The
reference holds one partition's Graph per process plus partition books for
remote lookup. Here all partitions live as ONE stacked, mesh-sharded array
set — shard p of the leading axis is partition p's local CSR:

  row_ids [P, R]   ascending owned global ids (INT_MAX-padded)
  indptr  [P, R+1] local CSR offsets over owned rows
  indices [P, E]   neighbor global ids (FILL-padded)
  eids    [P, E]   global edge ids
  weights [P, E]   optional edge weights

plus the two-level index over row_ids (ops/sorted_index.py):

  row_starts [P, (N >> shift) + 2]   first position per bucket of 2^shift ids

Row lookup inside a shard reads the two starts around the id and halves
``depth`` times between them (ops.uniform_sample_local); ``shift`` and
``depth`` follow from the table when the graph is built (``row_index``).
Cross-shard row access happens by routing seed ids with all_to_all, not by
pointer chasing — see DistNeighborSampler.
"""
from typing import Dict, Optional

import numpy as np

from ..ops import sorted_index
from ..typing import GraphPartitionData

INT32_MAX = np.iinfo(np.int32).max


def build_local_csr(part: GraphPartitionData, by: str = 'src'):
  """Partition edges -> (row_ids, indptr, indices, eids, weights) local CSR
  grouped by the owned endpoint."""
  ei = np.asarray(part.edge_index)
  key = ei[0] if by == 'src' else ei[1]
  other = ei[1] if by == 'src' else ei[0]
  order = np.argsort(key, kind='stable')
  key, other = key[order], other[order]
  eids = np.asarray(part.eids)[order]
  weights = (np.asarray(part.weights)[order]
             if part.weights is not None else None)
  row_ids, counts = np.unique(key, return_counts=True)
  indptr = np.zeros(row_ids.shape[0] + 1, dtype=np.int32)
  np.cumsum(counts, out=indptr[1:])
  return row_ids.astype(np.int32), indptr, other.astype(np.int32), \
      eids, weights


def _publish_indexes(indexes, shared: bool = False):
  """The gauges ``dist_graph.index_depth`` / ``dist_graph.index_bytes``:
  the halvings a row lookup runs (the deepest index) and the bytes a chip
  holds for the ``starts`` — none where the index is another owner's.
  Set once when a graph is built, never per batch."""
  from .. import metrics
  metrics.set_gauge('dist_graph.index_depth',
                    max((ix.depth for ix in indexes), default=0))
  metrics.set_gauge('dist_graph.index_bytes', 0 if shared else sum(
      4 * int(ix.starts.shape[-1]) for ix in indexes))


class DistGraph:
  """Stacked sharded partitions + partition book
  (reference: dist_graph.py:27-108).

  Args:
    num_partitions / partition_idx: parity fields (single host drives all
      partitions; partition_idx marks the host's first local one).
    parts: list of GraphPartitionData, one per partition.
    node_pb: [N] global node id -> owning partition.
    edge_pb: optional [E_total] edge id -> partition.
  """

  def __init__(self, num_partitions: int, partition_idx: int,
               parts, node_pb: np.ndarray,
               edge_pb: Optional[np.ndarray] = None, edge_dir: str = 'out'):
    self.num_partitions = num_partitions
    self.partition_idx = partition_idx
    self.node_pb = np.asarray(node_pb)
    self.edge_pb = edge_pb
    self.edge_dir = edge_dir

    by = 'src' if edge_dir == 'out' else 'dst'
    locs = [build_local_csr(p, by) for p in parts]
    r_max = max(l[0].shape[0] for l in locs)
    e_max = max(l[2].shape[0] for l in locs)
    p = len(locs)
    self.row_ids = np.full((p, r_max), INT32_MAX, np.int32)
    self.indptr = np.zeros((p, r_max + 1), np.int32)
    self.indices = np.full((p, e_max), -1, np.int32)
    self.eids = np.full((p, e_max), -1, np.int64)
    has_w = locs[0][4] is not None
    self.weights = np.zeros((p, e_max), np.float32) if has_w else None
    for i, (rid, ptr, ind, eid, w) in enumerate(locs):
      r, e = rid.shape[0], ind.shape[0]
      self.row_ids[i, :r] = rid
      self.indptr[i, :r + 1] = ptr
      self.indptr[i, r + 1:] = ptr[-1]
      self.indices[i, :e] = ind
      self.eids[i, :e] = eid
      if has_w:
        self.weights[i, :e] = w
    # the sampling programs are traced with its shift and depth
    self.row_index = sorted_index.build_sorted_index_host(self.row_ids,
                                                          self.num_nodes)
    _publish_indexes([self.row_index])

  @classmethod
  def from_device_shards(cls, mesh, node_pb, row_ids, indptr, indices,
                         eids=None, weights=None, edge_dir: str = 'out',
                         pb_dev=None, row_index=None):
    """A DistGraph over shards that ALREADY live on their devices: the
    stacked ``[P, ...]`` arrays of the module docstring, each sharded on
    its leading axis over ``mesh`` (shard p on device p), as
    :meth:`device_arrays` of a host-built graph would have placed them.
    Nothing of size E ever passes through host memory: a graph too large
    to stack on the host (the packed ``[P, E]`` arrays, twice with the
    caller's parts) is generated, or loaded shard by shard, straight onto
    the mesh and handed over here.

    ``node_pb`` ([N] global id -> partition) stays a host array — it is
    what the host-side book lookups read — and is placed replicated
    (``pb_dev``: that placement where the caller already made it, so a
    dataset keeps ONE book on the devices for its graph and stores).
    ``eids`` may be None when no consumer asks for edge ids
    (``with_edge=False`` samplers): a one-column placeholder stands in
    for the program argument. The index of ``row_ids`` is built by one
    program over the shards where they live; ``row_index`` is another
    owner's index over the SAME array (a feature store whose ``feat_ids``
    is this ``row_ids`` shares its ``_row_index``: no new bytes, no new
    program). The host-side tables of a host-built graph
    (``sorted_local_indices``, ``row_cumsum_stacked``) have nothing to
    read here and raise."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..utils import global_device_put
    self = cls.__new__(cls)
    shard = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    p = int(row_ids.shape[0])
    self.num_partitions = p
    self.partition_idx = 0
    self.node_pb = np.asarray(node_pb)
    self.edge_pb = None
    self.edge_dir = edge_dir
    self.row_ids = self.indptr = self.indices = self.eids = None
    self.weights = None
    for name, a in (('row_ids', row_ids), ('indptr', indptr),
                    ('indices', indices), ('eids', eids),
                    ('weights', weights)):
      if a is not None and not a.sharding.is_equivalent_to(shard, a.ndim):
        raise ValueError(
            f'DistGraph.from_device_shards: {name} is placed '
            f'{a.sharding}, not sharded on its leading axis over the '
            f'mesh ({shard})')
    if eids is None:
      eids = jax.jit(lambda: jnp.full((p, 1), -1, jnp.int32),
                     out_shardings=shard)()
    shared = row_index is not None
    if not shared:
      from ..utils.trace import record_dispatch
      record_dispatch('dist_graph.build_index')
      row_index = sorted_index.build_sorted_index_shards(
          mesh, row_ids, self.num_nodes)
    self.row_index = row_index
    _publish_indexes([row_index], shared)
    self._dev = dict(
        row_ids=row_ids, row_starts=row_index.starts, indptr=indptr,
        indices=indices, eids=eids,
        node_pb=pb_dev if pb_dev is not None else global_device_put(
            self.node_pb.astype(np.int32), NamedSharding(mesh, P())))
    if weights is not None:
      self._dev['weights'] = weights
    self._dev_mesh = mesh
    return self

  @property
  def on_device(self) -> bool:
    """True for a graph built by :meth:`from_device_shards`: its
    stacked arrays exist on the mesh only."""
    return getattr(self, '_dev', None) is not None

  def _host_only(self, what: str):
    if self.on_device:
      raise ValueError(
          f'DistGraph.{what} reads the host copy of the stacked CSR; a '
          'graph built by from_device_shards keeps none (weighted '
          'sampling and strict negatives need a host-built DistGraph)')

  @property
  def is_hetero(self) -> bool:
    return False

  @property
  def num_nodes(self) -> int:
    return int(self.node_pb.shape[0])

  def sorted_local_indices(self) -> np.ndarray:
    """[P, E] per-shard segment-sorted neighbor ids — the binary-search
    membership table for shard-local negative sampling
    (ops.random_negative_sample_local). Computed once, host-side."""
    self._host_only('sorted_local_indices')
    if not hasattr(self, '_sorted_loc'):
      out = np.full_like(self.indices, -1)
      for p in range(self.indices.shape[0]):
        ptr, ind = self.indptr[p], self.indices[p]
        nedges = int(ptr[-1])
        rows = np.repeat(np.arange(ptr.shape[0] - 1), np.diff(ptr))
        perm = np.lexsort((ind[:nedges], rows))
        out[p, :nedges] = ind[:nedges][perm]
      self._sorted_loc = out
    return self._sorted_loc

  def row_cumsum_stacked(self) -> np.ndarray:
    """[P, E] per-shard row-restarting cumulative edge weights — the
    inverse-CDF table for distributed weighted sampling
    (ops.weighted_sample_local)."""
    self._host_only('row_cumsum_stacked')
    assert self.weights is not None, 'graph has no edge weights'
    if not hasattr(self, '_wcum'):
      out = np.zeros_like(self.weights)
      for p in range(self.weights.shape[0]):
        ptr, w = self.indptr[p], self.weights[p]
        nedges = int(ptr[-1])
        cum = np.cumsum(w[:nedges])
        row_base = np.concatenate([[0.0], cum])[ptr[:-1]]
        counts = np.diff(ptr)
        base_per_edge = np.repeat(row_base, counts)
        out[p, :nedges] = cum - base_per_edge
      self._wcum = out
    return self._wcum

  def get_node_partitions(self, ids) -> np.ndarray:
    """Partition book lookup (reference: dist_graph.py:88-98)."""
    return self.node_pb[np.asarray(ids)]

  def get_edge_partitions(self, eids) -> Optional[np.ndarray]:
    """Reference: dist_graph.py:100-108."""
    if self.edge_pb is None:
      return None
    return self.edge_pb[np.asarray(eids)]

  def device_arrays(self, mesh):
    """Place the stacked arrays on the mesh: leading axis sharded over
    every mesh axis (flat 'g' or 2-axis ('slice', 'chip')), partition
    book replicated. Works on multi-host meshes (only this process's
    shards are placed — utils.global_device_put)."""
    if self.on_device:
      if mesh != self._dev_mesh:
        raise ValueError('DistGraph.device_arrays: the shards were '
                         'handed over on another mesh')
      return dict(self._dev)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..utils import global_device_put
    shard = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    repl = NamedSharding(mesh, P())
    out = dict(
        row_ids=global_device_put(self.row_ids, shard),
        row_starts=global_device_put(self.row_index.starts, shard),
        indptr=global_device_put(self.indptr, shard),
        indices=global_device_put(self.indices, shard),
        eids=global_device_put(self.eids, shard),
        node_pb=global_device_put(self.node_pb.astype(np.int32), repl),
    )
    if self.weights is not None:
      out['weights'] = global_device_put(self.weights, shard)
    return out


class DistHeteroGraph:
  """Heterogeneous sharded graph: one stacked local CSR per edge type plus
  per-node-type partition books.

  Reference: dist_graph.py holds Dict[EdgeType, Graph] + per-type PBs for
  the hetero path (dist_neighbor_sampler.py:287-319 routes each edge
  type's frontier by its source type's book). Same stacking re-design as
  :class:`DistGraph`, per edge type.

  Args:
    num_partitions / partition_idx: as DistGraph.
    parts: list (len P) of Dict[EdgeType, GraphPartitionData] — partition
      p's edges per type.
    node_pb: Dict[NodeType, [N_t]] global node id -> owning partition.
    edge_pb: optional Dict[EdgeType, [E_t]].
    edge_dir: 'out' (CSR by src) or 'in' (CSC by dst).
  """

  def __init__(self, num_partitions: int, partition_idx: int,
               parts, node_pb: Dict, edge_pb: Optional[Dict] = None,
               edge_dir: str = 'out'):
    self.num_partitions = num_partitions
    self.partition_idx = partition_idx
    self.node_pb = {t: np.asarray(pb) for t, pb in node_pb.items()}
    self.edge_pb = edge_pb
    self.edge_dir = edge_dir
    self.etypes = sorted({et for part in parts for et in part})
    self.ntypes = sorted(self.node_pb)

    by = 'src' if edge_dir == 'out' else 'dst'
    self.sub = {}
    empty = GraphPartitionData(edge_index=np.zeros((2, 0), np.int64),
                               eids=np.zeros((0,), np.int64))
    for et in self.etypes:
      g = DistGraph(num_partitions, partition_idx,
                    [part.get(et, empty) for part in parts],
                    self.node_pb[et[0] if edge_dir == 'out' else et[2]],
                    edge_dir=edge_dir)
      self.sub[et] = g
    # one index an edge type (id space: the row type's node count); the
    # gauges read the deepest and the sum, not the last one built
    _publish_indexes([g.row_index for g in self.sub.values()])

  @property
  def is_hetero(self) -> bool:
    return True

  def num_nodes(self, ntype) -> int:
    return int(self.node_pb[ntype].shape[0])

  def device_arrays(self, mesh):
    """{etype: stacked CSR arrays} + {'#pb': {ntype: replicated book}}."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())
    out = {et: g.device_arrays(mesh) for et, g in self.sub.items()}
    out['#pb'] = {t: jax.device_put(pb.astype(np.int32), repl)
                  for t, pb in self.node_pb.items()}
    return out
