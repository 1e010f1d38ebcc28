"""The mesh collate against ONE chip's HBM roofline (bound: bytes): the
bytes a chip's feature collate must move — every valid row of its shard
batch read once and written once, ``flops_mesh_node.collate_bytes``, counts
from the first chunk's replayed shard batches — over the peak HBM rate, as
a share of ``mesh_collate_ms``. The exchange's wire time is inside the
denominator: a row that crosses the interconnect cannot reach the HBM
rate, and this share says how far off the whole collate is. None, never 0,
when there is nothing to read."""
from perfbench import mesh_reduce

LAYER = 'collate'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  c = run['counts']
  ms = mesh_reduce.layer_ms(run, 'glt.collate')
  if not ms or not c['nodes']:
    return None
  need = run['cell'].collate_bytes(c['nodes'])
  return 100.0 * need / run['peaks']['hbm_bytes_per_s'] / (ms / 1e3)
