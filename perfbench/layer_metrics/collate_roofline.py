"""The collate gather against the HBM roofline (bound: bytes): the bytes
it must move — every valid row read once and written once,
perfbench/flops.py ``collate_bytes`` — over the peak HBM rate, as a share
of the collate program's device time in the per-batch slice."""
from perfbench import flops, trace_reduce

LAYER = 'collate'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  c = run['counts']
  ms = trace_reduce.program_ms_per_step(run['step'], 'jit_collate_batch')
  if ms is None or not c['nodes']:
    return None
  feat = run['cell'].feat
  need = flops.collate_bytes(sum(c['nodes']), feat.shape[1],
                             feat.dtype.itemsize)
  return 100.0 * need / run['peaks']['hbm_bytes_per_s'] / (ms / 1e3)
