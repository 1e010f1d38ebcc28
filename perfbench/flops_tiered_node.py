"""Operations and bytes a training step over a tiered feature table
REQUIRES, counted on valid rows (family ``tiered_node``): the model's
operations (``perfbench/flops.py``'s mean-SAGE count — the step after the
gather is the all-HBM step), and the bytes the tiered gather must move.

Never from padded buffer sizes, slab capacities or the searches a lookup
happens to run: those are the program's choices, not the algorithm's.
"""
from perfbench import flops

ID_BYTES = 4


def step_flops(model, nodes, edges):
  """Required operations of one training step on a batch with these valid
  counts per hop: ``flops.step_flops`` (the layered mean-SAGE count)."""
  if model['kind'] != 'sage':
    raise ValueError(
        f'flops_tiered_node: unknown model kind {model["kind"]!r}')
  return flops.step_flops(model, nodes, edges)


def gather_bytes(valid_rows, feat_dim, itemsize):
  """Bytes the tiered gather must move for ``valid_rows`` valid node
  slots: every valid row read once — from the hot prefix or from the
  chunk's slab, wherever it lives — and written once into the batch
  (``flops.collate_bytes``), and per slot its node id and its storage-row
  index read once each (the remap is the store's, so its read counts)."""
  return (flops.collate_bytes(valid_rows, feat_dim, itemsize) +
          2 * ID_BYTES * valid_rows)
