"""Device ms per step of the owners' side of the row exchange: self time
under ``glt.collate/exchange/lookup`` (``indexed_membership`` of every
received slot over ``feat_ids``) + ``/rows`` (the row gather, its mask, the
wire cast). The two ``all_to_all``s between the sides are ``/wire`` on the
line. None with a program that names no part."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return parts.ms(run, parts.EXCHANGE, ('lookup', 'rows'))
