from .base import (BaseSampler, EdgeSamplerInput, HeteroSamplerOutput,
                   NegativeSampling, NeighborOutput, NodeSamplerInput,
                   RemoteNodePathSamplerInput, RemoteSamplerInput,
                   SamplerOutput, SamplingConfig, SamplingType)
from .calibrate import (check_no_overflow, estimate_dist_frontier_caps,
                        estimate_frontier_caps,
                        estimate_hetero_frontier_caps, link_seed_width)
from .capacity import (DEFAULT_ETYPE, DEFAULT_NTYPE, CapacityPlan,
                       CapacityPlanError, ack_edge_ids)
from .negative_sampler import RandomNegativeSampler
from .neighbor_sampler import (NeighborSampler, hetero_tree_blocks,
                               hetero_tree_layout, tree_layout)
