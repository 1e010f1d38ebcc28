"""Device ms per step under ``glt.collate/exchange`` and under NONE of its
eight parts: fusions whose root instruction came from outside a part,
the ``conditional``'s own overhead. The honesty number of the split: the
parts and this add up to ``row_exchange_ms``. None with a program that
names no part."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return parts.ms(run, parts.EXCHANGE, (parts.UNSPLIT,))
