"""NeighborLoader: fanout-sampling node loader.

TPU-native port of
/root/reference/graphlearn_torch/python/loader/neighbor_loader.py: builds a
NeighborSampler from the Dataset and drives NodeLoader with it.
"""
from typing import Optional

from ..data import Dataset
from ..sampler import NeighborSampler
from .node_loader import NodeLoader


class NeighborLoader(NodeLoader):
  """Reference: loader/neighbor_loader.py:27-113."""

  def __init__(self, data: Dataset, num_neighbors, input_nodes,
               batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, with_edge: bool = False,
               with_weight: bool = False, strategy: str = 'random',
               collect_features: bool = True, to_device=None,
               seed: Optional[int] = None,
               node_budget: Optional[int] = None, dedup: str = 'auto',
               padded_window: Optional[int] = None,
               seed_labels_only: bool = False,
               frontier_caps=None, overflow_policy: str = 'raise',
               use_fused_hop=False):
    # frontier_caps='auto': calibrate in-loader against THIS loader's
    # seed pool and batch size (sampler.calibrate), so no caller ever
    # hand-computes calibration widths
    if isinstance(frontier_caps, str):
      if frontier_caps != 'auto':
        raise ValueError(f'frontier_caps={frontier_caps!r}: pass a list '
                         "of per-hop caps or 'auto'")
      if isinstance(data.graph, dict):
        # raised here so 'auto' on a hetero dataset fails clearly, not
        # with an AttributeError inside estimate_frontier_caps
        raise ValueError(
            "frontier_caps='auto' is homogeneous-only; on hetero "
            'datasets pass the {edge_type: [per-hop caps]} dict from '
            'calibrate.estimate_hetero_frontier_caps')
      from ..sampler.calibrate import estimate_frontier_caps
      pool = (input_nodes[1] if isinstance(input_nodes, tuple)
              else input_nodes)
      frontier_caps = estimate_frontier_caps(
          data.graph, list(num_neighbors), batch_size, input_nodes=pool,
          seed=seed or 0)
    sampler = NeighborSampler(
        data.graph, num_neighbors, device=to_device, with_edge=with_edge,
        with_weight=with_weight, strategy=strategy, edge_dir=data.edge_dir,
        seed=seed, node_budget=node_budget, dedup=dedup,
        padded_window=padded_window, frontier_caps=frontier_caps,
        use_fused_hop=use_fused_hop)
    super().__init__(data, sampler, input_nodes, batch_size, shuffle,
                     drop_last, with_edge, collect_features, to_device,
                     seed, seed_labels_only=seed_labels_only,
                     overflow_policy=overflow_policy)
