"""Device ms per step of the tiered gather: self time under
``glt.collate/tier`` in the traced slice — ``lookup`` (the id2index remap
and the membership search of every node slot over the chunk's sorted slab
ids), ``hot`` (the gather from the HBM hot prefix) and ``rows`` (the gather
from the staged slab and the select), the three on ``tier_reduce``'s
``perfbench:`` line. A part of ``tier_collate_ms``, not beside it. None with
a program that names no such part."""
from perfbench import tier_reduce

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return tier_reduce.gather_ms(run)
