from .collate import (collate_batch, collate_typed_batch, gather_rows,
                      stack2, stack2_batched, valid_mask)
from .gather_pallas import (decode_gather_plan, gather_rows_hbm,
                            gather_rows_hbm2, plan_gather_runs)
from .induce import InducerState, induce_next, init_empty, init_node
from .induce_map import (MapInducerState, induce_next_map, init_node_map)
from .induce_merge import (MergeInducerState, induce_next_merge,
                           init_empty_merge, init_node_merge)
from .induce_tree import (TreeInducerState, induce_next_tree,
                          init_empty_tree, init_node_tree)
from .negative import (random_negative_sample, random_negative_sample_local,
                       sort_csr_segments, sort_csr_segments_device)
from .neighbor import (BLOCK, build_padded_adjacency,
                       build_padded_adjacency_device, build_row_cumsum,
                       choose_padded_window, edge_in_csr,
                       padded_table_stats, uniform_sample,
                       uniform_sample_block, uniform_sample_local,
                       uniform_sample_padded, weighted_sample,
                       weighted_sample_local)
from .route import (exchange_capacity, gather_from_buckets, round8,
                    route_slots, scatter_to_buckets)
from .sample_fused import (LEVEL_MAX_CANDIDATES, build_indices128,
                           sample_hop_fused, sample_level_fused)
from .stitch import stitch_rows
from .subgraph import (node_subgraph, node_subgraph_bucketed,
                       node_subgraph_local)
from .unique import FILL, masked_unique, searchsorted_membership
