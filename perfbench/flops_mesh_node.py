"""Operations and bytes ONE chip's share of a mesh training step requires,
counted on valid rows (family ``mesh_node``): the model's operations on a
shard batch (``perfbench/flops.py``'s mean-SAGE count, the same layered
computation), the bytes the collate must move, and the bytes each exchange
carries off a chip.

Never from padded buffer sizes, except where the wire itself is padded: a
bucketed ``all_to_all`` sends its buckets whole, so ``wire`` bytes are
counted from the bucket shapes, ``valid`` bytes from what was asked for.
"""
from perfbench import flops


def step_flops(model, nodes, edges):
  """Required operations of one chip's training step on a shard batch
  with these valid counts per hop: ``flops.step_flops`` (the layered
  mean-SAGE count; the classifier's softmax left out)."""
  if model['kind'] != 'sage':
    raise ValueError(f'flops_mesh_node: unknown model kind {model["kind"]!r}')
  return flops.step_flops(model, nodes, edges)


#: bytes a chip's feature collate must move: every valid row of its shard
#: batch read once (from the cache, its own shard or a response bucket)
#: and written once into the batch
collate_bytes = flops.collate_bytes


def hop_exchange_bytes(rows_sent, fanout, id_bytes=4, mask_bytes=1):
  """Valid bytes one hop's exchange carries off a chip: each frontier id
  another shard expands goes out once and ``fanout`` neighbour ids with
  their validity come back."""
  return rows_sent * (id_bytes + fanout * (id_bytes + mask_bytes))


def row_exchange_bytes(rows_missed, parts, feat_dim, itemsize=4,
                       id_bytes=4):
  """Valid bytes the miss-only row exchange carries off a chip: of the
  unique missed rows a shard asks for, ``(P - 1) / P`` live on another
  chip (ids out, rows back at the wire dtype)."""
  return rows_missed * (parts - 1) / parts * (id_bytes + feat_dim * itemsize)


def allreduce_bytes(num_params, parts, itemsize=4):
  """Bytes a chip sends in a ring all-reduce of the gradients:
  ``2 (P - 1) / P`` times their size."""
  return 2 * (parts - 1) / parts * num_params * itemsize
