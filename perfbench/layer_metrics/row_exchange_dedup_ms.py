"""Device ms per step of the row exchange's miss dedup: self time under
``glt.collate/exchange/dedup`` — ``ops.masked_unique`` over the node
buffer's missed ids (its sort) and the request mask. None with a program
that names no part."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return parts.ms(run, parts.EXCHANGE, ('dedup',))
