"""Graph topology storage.

TPU-native re-design of /root/reference/graphlearn_torch/python/data/graph.py.

``Topology`` is the host-side CSR/CSC container (numpy) built from COO/CSR/CSC
input. ``Graph`` owns the device placement: on TPU the CSR arrays live in HBM
as jax Arrays (mode ``HBM``, the analog of the reference's CUDA/DMA mode), or
stay in host RAM until a sampler uploads them (mode ``CPU``); the reference's
ZERO_COPY (UVA pinned host
memory readable by the GPU) has no TPU equivalent, so ``ZERO_COPY`` is accepted
and mapped to ``HBM`` with the cold/overflow path handled by the feature store
instead.

Ids default to int32: TPU vector units and gathers are 2x cheaper in 32-bit and
every reference dataset's node count fits. Edge ids may exceed 2**31 on very
large graphs, so edge ids keep their input dtype.
"""
from typing import Optional, Tuple, Union

import numpy as np

from ..utils import coo_to_csr, csr_to_csc, ptr2ind

Layout = str  # 'COO' | 'CSR' | 'CSC'


class Topology:
  """CSR-or-CSC adjacency container (reference: data/graph.py:28-175).

  Args:
    edge_index: [2, E] COO (row, col), or (indptr, indices) when layout is
      'CSR'/'CSC'.
    edge_ids: optional [E] global edge ids (default: input position).
    edge_weights: optional [E] float weights.
    input_layout: layout of ``edge_index``.
    layout: storage layout, 'CSR' (out-edges grouped by src) or 'CSC'
      (in-edges grouped by dst).
    num_nodes: optional node count override.
  """

  def __init__(
      self,
      edge_index: Union[np.ndarray, Tuple[np.ndarray, np.ndarray]],
      edge_ids: Optional[np.ndarray] = None,
      edge_weights: Optional[np.ndarray] = None,
      input_layout: Layout = 'COO',
      layout: Layout = 'CSR',
      num_nodes: Optional[int] = None,
  ):
    if layout not in ('CSR', 'CSC'):
      raise ValueError(f'storage layout must be CSR or CSC, got {layout!r}')
    self.layout = layout
    input_layout = input_layout.upper()

    if input_layout == 'COO':
      row = np.asarray(edge_index[0]).reshape(-1)
      col = np.asarray(edge_index[1]).reshape(-1)
    elif input_layout in ('CSR', 'CSC'):
      indptr = np.asarray(edge_index[0]).reshape(-1)
      indices = np.asarray(edge_index[1]).reshape(-1)
      src = ptr2ind(indptr)
      if input_layout == 'CSR':
        row, col = src, indices
      else:
        row, col = indices, src
    else:
      raise ValueError(f'unknown input layout {input_layout!r}')

    if num_nodes is None:
      num_nodes = int(max(row.max(initial=-1), col.max(initial=-1))) + 1

    # Store grouped by src (CSR) or by dst (CSC).
    key, other = (row, col) if layout == 'CSR' else (col, row)
    indptr, indices, eids, weights = coo_to_csr(
        key, other, num_nodes, edge_ids, edge_weights)

    self.indptr = indptr.astype(np.int64)
    self.indices = indices.astype(np.int32)
    self.edge_ids = eids
    self.edge_weights = weights
    self._num_nodes = num_nodes

  @classmethod
  def from_csr(cls, indptr: np.ndarray, indices: np.ndarray,
               num_nodes: Optional[int] = None, layout: Layout = 'CSR'):
    """Adopt arrays that are ALREADY grouped (``indptr`` [N + 1],
    ``indices`` [>= E], rows in ``layout`` order) as they are: no sort,
    no copy where the dtypes fit, and NO edge ids or weights — the
    constructor's COO round trip sorts E keys and mints an int64 id per
    edge, which at half a billion edges is minutes and 8 GB for arrays
    a node job never reads. ``indices`` may be longer than
    ``indptr[-1]`` (a generator's padded tail): only the rows' ranges
    are ever read."""
    if layout not in ('CSR', 'CSC'):
      raise ValueError(f'storage layout must be CSR or CSC, got {layout!r}')
    self = cls.__new__(cls)
    self.layout = layout
    self.indptr = np.asarray(indptr).reshape(-1).astype(np.int64, copy=False)
    self.indices = np.asarray(indices).reshape(-1).astype(np.int32,
                                                          copy=False)
    self.edge_ids = self.edge_weights = None
    self._num_nodes = (int(self.indptr.shape[0]) - 1 if num_nodes is None
                       else int(num_nodes))
    return self

  @property
  def num_nodes(self) -> int:
    return self._num_nodes

  @property
  def num_edges(self) -> int:
    return int(self.indptr[-1])

  @property
  def degrees(self) -> np.ndarray:
    return np.diff(self.indptr)

  def degree(self, ids: np.ndarray) -> np.ndarray:
    ids = np.asarray(ids)
    return self.indptr[ids + 1] - self.indptr[ids]

  @property
  def max_degree(self) -> int:
    d = self.degrees
    return int(d.max()) if d.size else 0

  def to_coo(self) -> Tuple[np.ndarray, np.ndarray]:
    """Return (row, col) regardless of storage layout."""
    key = ptr2ind(self.indptr)
    if self.layout == 'CSR':
      return key, self.indices
    return self.indices, key

  def to_csc(self):
    """Return (indptr, indices, edge_ids, weights) of the transposed grouping."""
    return csr_to_csc(self.indptr, self.indices, self.edge_ids,
                      self.edge_weights)


class Graph:
  """Device-placed graph (reference: data/graph.py:178-297).

  Modes:
    'CPU'  — arrays stay in host numpy here; a sampler uploads them once
             to ITS process's default jax backend. The mode does not pick
             a backend: a process that must stay off the chip (a sampling
             server or worker beside a trainer) is started with
             JAX_PLATFORMS=cpu — one process per chip.
    'HBM'  — indptr/indices/eids/weights are jax Arrays resident in device
             HBM (reference CUDA 'DMA' mode analog).
    'ZERO_COPY' — accepted for API parity, maps to 'HBM' (no UVA on TPU; cold
             storage spillover is the feature store's job, see data/feature.py).

  Lazy init: device transfer happens on first access of ``indptr``/``indices``
  (reference lazy_init, data/graph.py:213).
  """

  def __init__(self, topo: Topology, mode: str = 'HBM', device=None,
               id_dtype=np.int32):
    mode = mode.upper()
    if mode == 'ZERO_COPY':
      mode = 'HBM'
    if mode == 'CUDA' or mode == 'DMA' or mode == 'DEVICE':
      mode = 'HBM'
    if mode not in ('CPU', 'HBM'):
      raise ValueError(f'unknown graph mode {mode!r}')
    self.topo = topo
    self.mode = mode
    self.device = device
    self.id_dtype = id_dtype
    self._indptr = None
    self._indices = None
    self._edge_ids = None
    self._edge_weights = None

  def lazy_init(self):
    if self._indptr is not None:
      return
    indptr = self.topo.indptr.astype(np.int32)
    indices = self.topo.indices.astype(self.id_dtype)
    eids = self.topo.edge_ids
    weights = self.topo.edge_weights
    if self.mode == 'HBM':
      import jax
      put = (lambda x: jax.device_put(x, self.device)) if self.device \
          else jax.device_put
      self._indptr = put(indptr)
      self._indices = put(indices)
      self._edge_ids = put(eids) if eids is not None else None
      self._edge_weights = put(weights) if weights is not None else None
    else:
      self._indptr = indptr
      self._indices = indices
      self._edge_ids = eids
      self._edge_weights = weights

  @property
  def indptr(self):
    self.lazy_init()
    return self._indptr

  @property
  def indices(self):
    self.lazy_init()
    return self._indices

  @property
  def edge_ids(self):
    self.lazy_init()
    return self._edge_ids

  @property
  def edge_weights(self):
    self.lazy_init()
    return self._edge_weights

  @property
  def num_nodes(self) -> int:
    return self.topo.num_nodes

  @property
  def num_edges(self) -> int:
    return self.topo.num_edges

  @property
  def layout(self) -> str:
    return self.topo.layout

  def degree(self, ids) -> np.ndarray:
    """Host-side degree lookup (reference: graph.cu LookupDegree)."""
    return self.topo.degree(np.asarray(ids))

  def share_ipc(self):
    """On TPU a single host process drives all local chips, so cross-process
    CUDA-IPC sharing (reference data/graph.py:287-297) reduces to sharing the
    host Topology; device arrays are rebuilt lazily in the consumer."""
    return self.topo, self.mode
