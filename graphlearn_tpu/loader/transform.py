"""Batch containers + SamplerOutput -> batch transforms.

TPU-native port of /root/reference/graphlearn_torch/python/loader/transform.py
(to_data / to_hetero_data). The reference emits torch_geometric
``Data``/``HeteroData``; this framework is torch-free on the hot path, so
`Data`/`HeteroData` here are light pytree-friendly containers holding jax (or
numpy) arrays, **kept at their padded static shapes** with validity masks so a
jitted train step compiles once. ``to_pyg()`` bridges to torch_geometric when
torch is wanted (reference parity for examples).
"""
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import numpy as np

from ..sampler import HeteroSamplerOutput, SamplerOutput
from ..typing import EdgeType, NodeType


@dataclass
class Data:
  """A sampled mini-batch subgraph (PyG-Data-shaped, fixed-shape + masks).

  node: [cap_n] global node ids (FILL-padded); local index == position.
  node_mask / num_nodes: validity of `node`.
  edge_index: [2, cap_e] relabeled (row=message source, col=target).
  edge_mask: [cap_e] validity.
  x / y: optional features [cap_n, F] / labels [cap_n].
  edge_ids / edge_attr: optional per-edge payloads.
  batch: [B] seed node ids; batch_size: number of real seeds.
  """
  node: Any
  num_nodes: Any = None
  node_mask: Any = None
  edge_index: Any = None
  edge_mask: Any = None
  x: Any = None
  y: Any = None
  edge_ids: Any = None
  edge_attr: Any = None
  batch: Any = None
  batch_size: Optional[int] = None
  num_sampled_nodes: Any = None
  num_sampled_edges: Any = None
  metadata: Dict[str, Any] = field(default_factory=dict)

  # pytree-ish convenience
  def __getattr__(self, item):
    md = object.__getattribute__(self, 'metadata')
    if item in md:
      return md[item]
    raise AttributeError(item)

  def to_pyg(self):
    """Exact-size torch_geometric.data.Data (drops padding). Optional torch
    bridge — reference emits these natively (transform.py:26-57)."""
    import torch
    from torch_geometric.data import Data as PygData
    node = np.asarray(self.node)
    n = int(self.num_nodes) if self.num_nodes is not None else node.shape[0]
    emask = np.asarray(self.edge_mask) if self.edge_mask is not None else None
    ei = np.asarray(self.edge_index)
    if emask is not None:
      ei = ei[:, emask]
    data = PygData(edge_index=torch.as_tensor(np.ascontiguousarray(ei)))
    data.node = torch.as_tensor(node[:n])
    if self.x is not None:
      data.x = torch.as_tensor(np.asarray(self.x)[:n])
    if self.y is not None:
      data.y = torch.as_tensor(np.asarray(self.y)[:n])
    if self.edge_ids is not None:
      e = np.asarray(self.edge_ids)
      data.edge_ids = torch.as_tensor(e[emask] if emask is not None else e)
    if self.batch is not None:
      data.batch = torch.as_tensor(np.asarray(self.batch))
    data.batch_size = self.batch_size
    for k, v in self.metadata.items():
      try:
        data[k] = torch.as_tensor(np.asarray(v))
      except Exception:
        pass
    return data


@dataclass
class HeteroData:
  """Hetero mini-batch: per-type dicts of the same padded payloads."""
  node: Dict[NodeType, Any]
  num_nodes: Dict[NodeType, Any] = None
  edge_index: Dict[EdgeType, Any] = None
  edge_mask: Dict[EdgeType, Any] = None
  x: Dict[NodeType, Any] = None
  y: Dict[NodeType, Any] = None
  edge_ids: Dict[EdgeType, Any] = None
  edge_attr: Dict[EdgeType, Any] = None
  batch: Dict[NodeType, Any] = None
  batch_size: Optional[int] = None
  num_sampled_nodes: Any = None
  num_sampled_edges: Any = None
  metadata: Dict[str, Any] = field(default_factory=dict)

  def __getattr__(self, item):
    md = object.__getattribute__(self, 'metadata')
    if item in md:
      return md[item]
    raise AttributeError(item)


def to_data(out: SamplerOutput, node_feats=None, node_labels=None,
            edge_feats=None, node_mask=None, edge_index=None) -> Data:
  """SamplerOutput -> Data (reference: transform.py:26-57). Keeps padding.

  ``node_mask``/``edge_index`` may be passed precomputed (loaders derive
  them inside the jitted ops.collate_batch so no eager op touches pending
  sampler outputs); when absent they are derived here.
  """
  from .. import ops
  node = out.node
  if node_mask is None and out.num_nodes is not None:
    node_mask = ops.valid_mask(node, out.num_nodes)
  ei = edge_index
  if ei is None and out.row is not None:
    ei = ops.stack2(out.row, out.col)
  return Data(
      node=node, num_nodes=out.num_nodes, node_mask=node_mask,
      edge_index=ei, edge_mask=out.edge_mask, x=node_feats, y=node_labels,
      edge_ids=out.edge, edge_attr=edge_feats, batch=out.batch,
      batch_size=out.batch_size, num_sampled_nodes=out.num_sampled_nodes,
      num_sampled_edges=out.num_sampled_edges, metadata=dict(out.metadata))


def to_hetero_data(out: HeteroSamplerOutput, node_feats=None,
                   node_labels=None, edge_feats=None) -> HeteroData:
  """HeteroSamplerOutput -> HeteroData (reference: transform.py:60-136)."""
  from .. import ops
  ei = None
  if out.row is not None:
    # jitted per-etype stack: no eager op on pending sampler outputs
    ei = {et: ops.stack2(r, out.col[et]) for et, r in out.row.items()}
  return HeteroData(
      node=out.node, num_nodes=out.num_nodes, edge_index=ei,
      edge_mask=out.edge_mask, x=node_feats, y=node_labels,
      edge_ids=out.edge, batch=out.batch, batch_size=out.batch_size,
      num_sampled_nodes=out.num_sampled_nodes,
      num_sampled_edges=out.num_sampled_edges,
      metadata=dict(out.metadata, input_type=out.input_type))
