"""The collate gather against the HBM roofline (bound: bytes) INSIDE the
window's own chunk program: the bytes it must move — every valid row
read once and written once, perfbench/flops.py ``collate_bytes``, counts
from the first chunk's replayed batches — over the peak HBM rate, as a share of
``scan_collate_ms``. None, never 0, when there is nothing to read."""
from perfbench import flops, scope_reduce

LAYER = 'collate'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  c = run['counts']
  ms = scope_reduce.layer_ms(run, 'glt.collate')
  if not ms or not c['nodes']:
    return None
  feat = run['cell'].feat
  need = flops.collate_bytes(sum(c['nodes']), feat.shape[1],
                             feat.dtype.itemsize)
  return 100.0 * need / run['peaks']['hbm_bytes_per_s'] / (ms / 1e3)
