"""The typed collate against the HBM roofline (bound: bytes) INSIDE the
typed window's own chunk program: the bytes it must move — per node type
every valid row read once from that type's table and written once into the
batch (the family's ``collate_bytes`` = perfbench/flops_hetero_node.py,
counts from the first chunk's replayed batches, rows in the table's own
dtype) — over the peak HBM rate, as a share of ``typed_collate_ms``. None,
never 0, when there is nothing to read."""
from perfbench import scope_reduce

LAYER = 'collate'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  ms = scope_reduce.layer_ms(run, 'glt.collate')
  if not ms or not run['counts']['nodes']:
    return None
  need = run['cell'].collate_bytes()
  return 100.0 * need / run['peaks']['hbm_bytes_per_s'] / (ms / 1e3)
