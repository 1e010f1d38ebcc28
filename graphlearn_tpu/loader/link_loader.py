"""Link (edge-seed) loader base.

TPU-native port of /root/reference/graphlearn_torch/python/loader/link_loader.py:
iterates seed edges, runs link sampling (negatives + node expansion), and
collates edge_label_index / edge_label (binary) or src/dst_pos/dst_neg
indices (triplet) into the batch metadata — same contract as the reference's
deduced edge_label_index (link_loader.py:100-229).
"""
from typing import Optional

import numpy as np

from ..data import Dataset
from ..sampler import BaseSampler, EdgeSamplerInput, NegativeSampling
from .node_loader import NodeLoader, SeedBatcher


def _on_device(x) -> bool:
  """A jax array, or a pair of them."""
  import jax
  if isinstance(x, (tuple, list)):
    return len(x) == 2 and all(isinstance(a, jax.Array) for a in x)
  return isinstance(x, jax.Array)


class LinkLoader(NodeLoader):
  """Reference: loader/link_loader.py:35-229."""

  def __init__(self, data: Dataset, link_sampler: BaseSampler,
               edge_label_index, edge_label=None,
               neg_sampling: Optional[NegativeSampling] = None,
               batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, with_edge: bool = False,
               collect_features: bool = True, to_device=None,
               seed: Optional[int] = None,
               overflow_policy: str = 'raise'):
    from ..typing import split_edge_type_seeds
    self.edge_type, edge_label_index = \
        split_edge_type_seeds(edge_label_index)
    # seed edges stay where the caller holds them: a device array (or a
    # (rows, cols) pair of device arrays) is never fetched — a seed set
    # of every edge of a large graph is built on the device and stays
    # there; anything else becomes host numpy, as the reference's
    eli = edge_label_index
    if not _on_device(eli):
      eli = np.asarray(eli)
    self.rows, self.cols = eli[0].reshape(-1), eli[1].reshape(-1)
    self.edge_label = (np.asarray(edge_label).reshape(-1)
                       if edge_label is not None else None)
    self.neg_sampling = (NegativeSampling.cast(neg_sampling)
                         if neg_sampling is not None else None)
    self.data = data
    self.sampler = link_sampler
    self.batch_size = batch_size
    self.collect_features = collect_features
    self.to_device = to_device
    self.input_type = self.edge_type
    self._init_overflow_policy(overflow_policy)
    self._batcher = SeedBatcher(len(self.rows), batch_size, shuffle,
                                drop_last, seed)
    del with_edge

  def seed_pairs_device(self):
    """``(rows, cols)`` as device arrays, uploaded once: what a scanned
    epoch gathers a step's seed pairs from."""
    import jax.numpy as jnp
    if getattr(self, '_pairs_dev', None) is None:
      as_dev = lambda a: a if _on_device(a) else jnp.asarray(
          np.asarray(a, dtype=np.int32))
      self._pairs_dev = (as_dev(self.rows), as_dev(self.cols))
    return self._pairs_dev

  def __iter__(self):
    guarded, recompute = self._overflow_epoch_start()
    for idx in self._batcher:
      inputs = EdgeSamplerInput(
          row=self.rows[idx], col=self.cols[idx],
          label=self.edge_label[idx] if self.edge_label is not None else
          None, input_type=self.edge_type, neg_sampling=self.neg_sampling)
      if recompute:
        key = self.sampler._next_key()
        out = self.sampler.sample_from_edges(inputs, key=key)
        if self._batch_overflowed(out):
          self.overflow_recomputes += 1
          out = self._replay_sampler().sample_from_edges(inputs, key=key)
      else:
        out = self.sampler.sample_from_edges(inputs)
        if guarded:
          self._accumulate_overflow(out)
      yield self._collate_fn(out)
    if guarded and not recompute:
      self._finish_epoch_overflow()
