"""Random negative edge sampler wrapper.

TPU-native port of
/root/reference/graphlearn_torch/python/sampler/negative_sampler.py: thin
object API over the fixed-shape negative-sampling op (ops/negative.py), with
the edge_dir='in' row/col flip (CSC stores (dst, src) pairs).
"""
from typing import Optional


from .. import ops
from ..data import Graph


class RandomNegativeSampler:
  """Sample (src, dst) pairs absent from the graph
  (reference: negative_sampler.py:21-57)."""

  def __init__(self, graph: Graph, mode: str = 'binary',
               edge_dir: str = 'out', seed: Optional[int] = None):
    import jax
    self.graph = graph
    self.mode = mode
    self.edge_dir = edge_dir
    # counter-addressed PRNG (never split-and-carry): call N's key is
    # fold_in(base, N), so any stream position is reachable from
    # (base_key, integer) alone — the replay discipline every sampler
    # in this package follows (docs/failure_model.md)
    self._key = jax.random.PRNGKey(0 if seed is None else seed)
    self._call_count = 0
    self._sorted_indices = ops.sort_csr_segments_device(
        graph.topo.indptr, graph.indices)

  def sample(self, num_samples: int, trials: int = 5,
             padding: bool = False):
    """Returns (rows, cols, mask); with ``padding`` the output is always
    full (non-strict mode, reference random_negative_sampler.cu)."""
    import jax
    g = self.graph
    self._call_count += 1
    sub = jax.random.fold_in(self._key, self._call_count)
    rows, cols, mask = ops.random_negative_sample(
        g.indptr, self._sorted_indices, g.num_nodes, g.num_nodes,
        num_samples, sub, trials=trials, padding=padding)
    if self.edge_dir == 'in':
      rows, cols = cols, rows
    return rows, cols, mask
