"""Distributed dataset: partitioned graph + features + books on the mesh.

TPU-native re-design of
/root/reference/graphlearn_torch/python/distributed/dist_dataset.py. The
reference process loads ITS partition from the partition dir and keeps
partition books for the rest. On TPU one host process drives all local
chips, so `load()` loads every partition this host serves and stacks them
into the mesh-sharded DistGraph / DistFeature containers; the hot-cache is
merged via cat_feature_cache exactly like the reference (dist_dataset.py:
78-167), moving cached entries' feature-PB ownership.
"""
from typing import Optional

import numpy as np

from ..partition import cat_feature_cache, load_partition
from .dist_feature import DistFeature
from .dist_graph import DistGraph


class DistDataset:
  """Reference: dist_dataset.py:30-226 (homogeneous path)."""

  def __init__(self, num_partitions: int = 1, partition_idx: int = 0,
               dist_graph: Optional[DistGraph] = None,
               dist_feature: Optional[DistFeature] = None,
               node_labels=None, node_feat_pb=None, edge_dir: str = 'out',
               edge_features: Optional[DistFeature] = None):
    self.num_partitions = num_partitions
    self.partition_idx = partition_idx
    self.graph = dist_graph
    self.node_features = dist_feature
    self.edge_features = edge_features
    self.node_labels = node_labels
    self.node_feat_pb = node_feat_pb
    self.edge_dir = edge_dir

  @classmethod
  def from_device_shards(cls, mesh, node_pb, graph: dict, features: dict,
                         labels=None, edge_dir: str = 'out',
                         split_ratio: float = 0.0, cache_rows=None,
                         hotness=None, wire_dtype=None, bucket_frac=2.0):
    """A homogeneous dataset over shards that already live on their
    devices (``DistGraph.from_device_shards`` /
    ``DistFeature.from_device_shards``): a partitioned graph too large
    to stack in host memory is generated, or loaded shard by shard,
    straight onto the mesh, and nothing of size N x F or E passes
    through the host.

    ``graph``: ``row_ids``, ``indptr``, ``indices`` (and optionally
    ``eids``, ``weights``), each ``[P, ...]`` sharded on its leading
    axis. ``features``: ``feat_ids`` ``[P, n_max]`` and ``feats``
    ``[P, n_max, F]``. ``labels``: an ``[P, n_max]`` array in the order
    of ``feat_ids`` (kept on the devices as a one-column store that
    shares the feature store's id table, its index and the book), or a
    host ``[N]`` array as the constructor takes. Where ``graph['row_ids']``
    and ``features['feat_ids']`` are ONE array the graph shares the
    store's two-level index over it; otherwise it builds its own.
    ``node_pb`` is the one host array: it routes the features too.
    ``hotness`` ranks the rows for the hot cache (``split_ratio`` /
    ``cache_rows``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..utils import global_device_put
    # one book on the devices for the graph and both stores, not a copy
    # each (N int32 replicated on every chip)
    pb_dev = global_device_put(np.asarray(node_pb).astype(np.int32),
                               NamedSharding(mesh, P()))
    df = DistFeature.from_device_shards(
        mesh, node_pb, features['feat_ids'], features['feats'],
        split_ratio=split_ratio, cache_rows=cache_rows, hotness=hotness,
        wire_dtype=wire_dtype, bucket_frac=bucket_frac, pb_dev=pb_dev)
    # every node a row owner: the graph's row table IS the store's id
    # table (one array handed over for both), so is its index
    shared = graph['row_ids'] is features['feat_ids']
    dg = DistGraph.from_device_shards(
        mesh, node_pb, edge_dir=edge_dir, pb_dev=pb_dev,
        row_index=df._row_index if shared else None, **graph)
    if labels is not None and getattr(labels, 'ndim', 1) == 2:
      labels = DistFeature.from_device_shards(
          mesh, node_pb, features['feat_ids'], labels[..., None],
          pb_dev=pb_dev, row_index=df._row_index)
    return cls(dg.num_partitions, 0, dg, df, node_labels=labels,
               node_feat_pb=np.asarray(node_pb), edge_dir=edge_dir)

  def load(self, root_dir: str, mesh=None, node_labels=None,
           edge_dir: str = 'out', feature_dtype=None,
           feature_with_cache: bool = True, split_ratio: float = 0.0,
           cache_rows=None, hotness='in_degree', wire_dtype=None,
           bucket_frac=2.0, feature_spill_dir=None):
    """Load all partitions of `root_dir` and shard them over `mesh`
    (reference: DistDataset.load, dist_dataset.py:78-167). Handles both
    the homogeneous and the heterogeneous (per-type) partition layouts of
    partition/base.py.

    ``split_ratio``/``cache_rows`` mirror the local ``data.Feature``
    knobs: a non-zero value replicates that share of the globally
    hottest feature rows per shard (DistFeature hot cache) so only
    cache misses cross the interconnect. ``hotness`` ranks the rows:
    'in_degree' (default) bincounts edge destinations across the loaded
    partitions; pass explicit [N] scores (per type for hetero) for
    presampling-frequency hotness, or None to cache the lowest ids.
    ``wire_dtype``/``bucket_frac`` tune the miss exchange (see
    DistFeature). ``feature_spill_dir`` builds the NODE feature stores
    as ``storage.TieredDistFeature`` instead: partition row payloads
    spill to memory-mapped disk tiers under that directory and host
    RAM keeps only the routing structures + hot cache — the
    out-of-core shard layout (docs/storage.md)."""
    num_parts, g0, nf0, ef0, node_pb, edge_pb = load_partition(root_dir, 0)
    if mesh is None:
      from .dist_context import get_context
      ctx = get_context()
      mesh = ctx.mesh if ctx else None
    parts = [g0]
    nfeats = [nf0]
    efeats = [ef0]
    for p in range(1, num_parts):
      _, g, nf, ef, _, _ = load_partition(root_dir, p)
      parts.append(g)
      nfeats.append(nf)
      efeats.append(ef)

    self.num_partitions = num_parts
    self.edge_dir = edge_dir
    with_cache = split_ratio > 0 or cache_rows is not None

    def _in_degree(num_nodes, ntype=None):
      """In-degree hotness from the loaded partitions' edge cols (the
      ids sampling touches as neighbors)."""
      deg = np.zeros((num_nodes,), np.int64)
      for g in parts:
        ets = ([et for et in g if et[2] == ntype] if isinstance(g, dict)
               else [None])
        for et in ets:
          cols = (g[et] if et is not None else g).edge_index[1]
          np.add.at(deg, np.clip(cols, 0, num_nodes - 1), 1)
      return deg

    def _hotness(num_nodes, ntype=None):
      if not with_cache:
        return None
      if isinstance(hotness, str):
        assert hotness == 'in_degree', hotness
        return _in_degree(num_nodes, ntype)
      if isinstance(hotness, dict):
        return hotness.get(ntype) if hotness else None
      return hotness

    feat_kw = dict(mesh=mesh, dtype=feature_dtype, wire_dtype=wire_dtype,
                   bucket_frac=bucket_frac)
    cache_kw = dict(split_ratio=split_ratio, cache_rows=cache_rows)

    def _node_store_cls(subdir):
      """(class, extra kwargs) for a node feature store: RAM-resident
      DistFeature, or the disk-backed tiered variant when a spill dir
      is configured."""
      if feature_spill_dir is None:
        return DistFeature, {}
      import os

      from ..storage.dist import TieredDistFeature
      return TieredDistFeature, {
          'spill_dir': os.path.join(feature_spill_dir, subdir)}
    if isinstance(g0, dict):
      from .dist_graph import DistHeteroGraph
      self.graph = DistHeteroGraph(num_parts, 0, parts, node_pb,
                                   edge_pb or None, edge_dir)
      if nf0:
        self.node_features = {}
        self.node_feat_pb = {}
        for nt in nf0:
          feat_pb = node_pb[nt].astype(np.int32).copy()
          blocks = []
          for p, nf in enumerate(nfeats):
            nft = nf[nt]
            if feature_with_cache and nft.cache_feats is not None:
              feats, ids, feat_pb = cat_feature_cache(p, nft, feat_pb)
            else:
              feats, ids = nft.feats, nft.ids
            blocks.append((ids, feats))
          self.node_feat_pb[nt] = feat_pb
          cls, extra = _node_store_cls(f'node_{nt}')
          self.node_features[nt] = cls(
              num_parts, blocks, node_pb[nt],
              hotness=_hotness(node_pb[nt].shape[0], nt), **cache_kw,
              **feat_kw, **extra)
      if ef0:
        self.edge_features = {}
        for et in ef0:
          self.edge_features[et] = DistFeature(
              num_parts,
              [(ef[et].ids, ef[et].feats) for ef in efeats],
              edge_pb[et], **feat_kw)
    else:
      self.graph = DistGraph(num_parts, 0, parts, node_pb, edge_pb,
                             edge_dir)
      if nf0 is not None:
        feat_pb = node_pb.astype(np.int32).copy()
        blocks = []
        for p, nf in enumerate(nfeats):
          if feature_with_cache and nf.cache_feats is not None:
            feats, ids, feat_pb = cat_feature_cache(p, nf, feat_pb)
          else:
            feats, ids = nf.feats, nf.ids
          blocks.append((ids, feats))
        self.node_feat_pb = feat_pb
        cls, extra = _node_store_cls('node')
        self.node_features = cls(
            num_parts, blocks, node_pb,
            hotness=_hotness(node_pb.shape[0]), **cache_kw, **feat_kw,
            **extra)
        # note: lookups route by the *graph* node_pb (each id's canonical
        # owner); the cache raises the chance the row is also local, but
        # canonical routing keeps responses unique. The feature pb with
        # cache entries is kept for host-side locality decisions.
      if ef0 is not None:
        # edge features: sharded by the edge book (reference DistDataset
        # keeps an edge Feature + edge_feat_pb, dist_dataset.py:149-162)
        self.edge_features = DistFeature(
            num_parts, [(ef.ids, ef.feats) for ef in efeats], edge_pb,
            **feat_kw)
    if node_labels is not None:
      self.node_labels = (node_labels if isinstance(node_labels, dict)
                          else np.asarray(node_labels))
    return self

  def feature_stores(self):
    """Every DistFeature this dataset owns (node + edge, flattened over
    the per-type dicts) — the discovery point for epoch-granularity
    stats publishing: the collocated loaders and the scanned-epoch
    trainer both drain the on-device accumulators through this list
    (an unread int32 accumulator would eventually wrap). The sampler's
    label stores are NOT dataset-owned — loaders drain those via
    sampler.label_stores()."""
    for store in (self.node_features, self.edge_features):
      for f in (store.values() if isinstance(store, dict) else [store]):
        if hasattr(f, 'publish_stats'):
          yield f

  @property
  def node_pb(self):
    return self.graph.node_pb if self.graph is not None else None
