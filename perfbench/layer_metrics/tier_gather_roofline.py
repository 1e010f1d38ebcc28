"""The tiered gather against the HBM roofline (bound: bytes): the bytes it
must move — every valid row of the batch read once, from the hot prefix or
the slab, and written once, and per valid slot its node id and its
storage-row index (``perfbench/flops_tiered_node.gather_bytes``, counts
from the first chunk's replayed batches) — over the peak HBM rate, as a
share of ``tier_gather_ms``. What the program does beyond that (both
tables read for every slot, pads included, twenty search rounds) is in the
denominator only. None, never 0, when the scope is absent or nothing was
counted."""
from perfbench import tier_reduce

LAYER = 'collate'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  c = run['counts']
  ms = tier_reduce.gather_ms(run)
  if not ms or not c.get('nodes'):
    return None
  need = run['cell'].gather_bytes(c['nodes'])
  return 100.0 * need / run['peaks']['hbm_bytes_per_s'] / (ms / 1e3)
