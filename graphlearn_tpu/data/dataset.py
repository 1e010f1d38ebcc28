"""Dataset: graphs + features + labels, homogeneous or heterogeneous.

TPU-native port of /root/reference/graphlearn_torch/python/data/dataset.py.
Semantics kept: ``edge_dir`` decides CSR (out-edges) vs CSC (in-edges)
storage (dataset.py:103-113); node features may be hotness-reordered via
``sort_by_in_degree`` with the ``id2index`` map threaded into the Feature
store (dataset.py:160-174); hetero graphs/features are dicts keyed by
EdgeType/NodeType. Tensors are numpy host-side; device placement happens in
Graph/Feature lazily.
"""
from typing import Dict, Optional, Tuple, Union

import numpy as np

from ..typing import EdgeType, NodeType
from .feature import Feature
from .graph import Graph, Topology


class Dataset:
  """Aggregate of graph(s), node/edge features and labels
  (reference: data/dataset.py:29-353)."""

  def __init__(self, graph=None, node_features=None, edge_features=None,
               node_labels=None, edge_dir: str = 'out'):
    self.graph: Union[Graph, Dict[EdgeType, Graph], None] = graph
    self.node_features: Union[Feature, Dict[NodeType, Feature], None] = \
        node_features
    # learned rows for node types that have no feature rows
    # (init_node_tables): {ntype: (num_rows, width)}
    self.node_tables: Optional[Dict[NodeType, Tuple[int, int]]] = None
    self.edge_features: Union[Feature, Dict[EdgeType, Feature], None] = \
        edge_features
    self.node_labels = node_labels
    self.edge_dir = edge_dir

  # -- graph init ----------------------------------------------------------

  def init_graph(self, edge_index=None, edge_ids=None, edge_weights=None,
                 layout='COO', graph_mode='HBM', device=None,
                 num_nodes=None):
    """Build Graph(s) from edge index input (reference: dataset.py:46-115).

    ``edge_dir='out'`` stores CSR (neighbors = out-edges, grouped by src);
    ``edge_dir='in'`` stores CSC (neighbors = in-edges, grouped by dst).
    Hetero input: dicts keyed by EdgeType.
    """
    if edge_index is None:
      return self
    store_layout = 'CSR' if self.edge_dir == 'out' else 'CSC'

    def build(ei, eids, ew, n):
      topo = Topology(ei, eids, ew, input_layout=layout,
                      layout=store_layout, num_nodes=n)
      return Graph(topo, graph_mode, device)

    if isinstance(edge_index, dict):
      self.graph = {}
      for etype, ei in edge_index.items():
        eids = edge_ids.get(etype) if isinstance(edge_ids, dict) else None
        ew = (edge_weights.get(etype)
              if isinstance(edge_weights, dict) else None)
        n = num_nodes.get(etype) if isinstance(num_nodes, dict) else num_nodes
        self.graph[etype] = build(ei, eids, ew, n)
    else:
      self.graph = build(edge_index, edge_ids, edge_weights, num_nodes)
    return self

  # -- feature init --------------------------------------------------------

  def init_node_features(self, node_feature_data=None, id2idx=None,
                         sort_func=None, split_ratio: float = 1.0,
                         device_group_list=None, device=None,
                         with_device: bool = True, dtype=None):
    """Build node Feature store(s) (reference: dataset.py:117-178).

    When ``sort_func`` (e.g. :func:`sort_by_in_degree`) is given and no
    explicit ``id2idx``, rows are hotness-reordered and the produced
    id2index map is installed in the store.

    ``split_ratio`` defaults to 1.0 (all rows HBM-resident). The reference
    defaults to 0.0 because its CPU rows stay device-readable through UVA;
    TPU has no UVA, so device-resident is the default and the ratio is the
    knob for tables larger than HBM (cold tail served from host).
    """
    if node_feature_data is None:
      return self

    def build(feat, topo, i2i):
      feat = np.asarray(feat)
      if sort_func is not None and i2i is None and topo is not None:
        feat, i2i = sort_func(feat, split_ratio, topo)
      return Feature(feat, split_ratio, device_group_list, device,
                     with_device, i2i, dtype)

    if isinstance(node_feature_data, dict):
      self.node_features = {}
      for ntype, feat in node_feature_data.items():
        topo = self._topo_for_node_type(ntype)
        i2i = id2idx.get(ntype) if isinstance(id2idx, dict) else None
        self.node_features[ntype] = build(feat, topo, i2i)
    else:
      topo = self.graph.topo if isinstance(self.graph, Graph) else None
      self.node_features = build(node_feature_data, topo, id2idx)
    return self

  def init_node_tables(self, tables):
    """Learned rows for node types that have no feature rows: ``{ntype:
    (num_rows, width)}``, float32. The rows are not data but parameters
    of the train state (``models/tables.py``): the model declares the
    same tables (``models.RGNN(node_tables=dataset.node_tables)``) and
    makes them from its init key, a typed batch carries those types'
    node ids where it would carry rows, and the optimizer steps over
    them with the weights. ``loader.ScanTrainer`` over a typed
    ``NodeLoader`` trains them, and the per-batch typed loader emits the
    ids; the other scanned trainers refuse such a loader
    (``docs/capacity_plans.md``, "Learned node tables")."""
    tables = {t: (int(r), int(w)) for t, (r, w) in dict(tables).items()}
    stores = self.node_features if isinstance(self.node_features,
                                              dict) else {}
    both = sorted(set(tables) & set(stores))
    if both:
      raise ValueError(f'node types {both} have feature rows already; a '
                       'learned table is for a type that has none')
    if any(r < 1 or w < 1 for r, w in tables.values()):
      raise ValueError(f'a learned table has rows and width >= 1: {tables}')
    self.node_tables = tables
    return self

  def init_edge_features(self, edge_feature_data=None, split_ratio=1.0,
                         device_group_list=None, device=None,
                         with_device: bool = True, dtype=None):
    """Edge feature stores, keyed by edge id (reference: dataset.py:180-220).
    No hotness reorder (edge ids are already partition-local contiguous)."""
    if edge_feature_data is None:
      return self
    if isinstance(edge_feature_data, dict):
      self.edge_features = {
          etype: Feature(np.asarray(f), split_ratio, device_group_list,
                         device, with_device, None, dtype)
          for etype, f in edge_feature_data.items()}
    else:
      self.edge_features = Feature(np.asarray(edge_feature_data), split_ratio,
                                   device_group_list, device, with_device,
                                   None, dtype)
    return self

  def init_node_labels(self, node_label_data=None):
    if node_label_data is not None:
      if isinstance(node_label_data, dict):
        self.node_labels = {k: np.asarray(v)
                            for k, v in node_label_data.items()}
      else:
        self.node_labels = np.asarray(node_label_data)
    return self

  # -- accessors (reference: dataset.py:222-331) ---------------------------

  def get_graph(self, etype: Optional[EdgeType] = None):
    if isinstance(self.graph, dict):
      return self.graph.get(etype) if etype is not None else None
    return self.graph

  def get_node_feature(self, ntype: Optional[NodeType] = None):
    if isinstance(self.node_features, dict):
      return self.node_features.get(ntype) if ntype is not None else None
    return self.node_features

  def get_edge_feature(self, etype: Optional[EdgeType] = None):
    if isinstance(self.edge_features, dict):
      return self.edge_features.get(etype) if etype is not None else None
    return self.edge_features

  def get_node_label(self, ntype: Optional[NodeType] = None):
    if isinstance(self.node_labels, dict):
      return self.node_labels.get(ntype) if ntype is not None else None
    return self.node_labels

  def get_node_types(self):
    if isinstance(self.graph, dict):
      ntypes = []
      for (src, _, dst) in self.graph.keys():
        for t in (src, dst):
          if t not in ntypes:
            ntypes.append(t)
      return ntypes
    return None

  def get_edge_types(self):
    if isinstance(self.graph, dict):
      return list(self.graph.keys())
    return None

  @property
  def is_hetero(self) -> bool:
    return isinstance(self.graph, dict)

  def _topo_for_node_type(self, ntype: NodeType):
    """Topology whose *key* axis is this node type, for in-degree hotness.

    With edge_dir='in' the stored CSC is grouped by dst, so a graph whose
    dst type == ntype gives in-degrees directly; mirrored for 'out'.
    """
    if not isinstance(self.graph, dict):
      return None
    for (src, _, dst), g in self.graph.items():
      key_type = src if self.edge_dir == 'out' else dst
      if key_type == ntype:
        return g.topo
    return None

  def share_ipc(self):
    """Single host process drives all TPU chips; sharing = handing host
    containers over (reference dataset.py:237,342-353)."""
    return self
