"""Dense Adam's sweep over the learned tables against the HBM roofline
(bound: bytes): per table entry the gradient, the parameter and the two
moments read and the three written — 7 x rows x width x 4 B a step
(``perfbench/flops_hetero_table_node.table_update_bytes``) — over the peak
HBM rate, as a share of ``table_update_ms``. None, never 0, when the scope
is absent."""
from perfbench import table_reduce

LAYER = 'model'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  ms = table_reduce.part_ms(run, 'update')
  if not ms:
    return None
  need = run['cell'].table_update_bytes()
  return 100.0 * need / run['peaks']['hbm_bytes_per_s'] / (ms / 1e3)
