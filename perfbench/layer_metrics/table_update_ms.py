"""Device ms per step of the optimizer over the table leaves — dense Adam's
sweep over every row of every table, read or not: self time under
``glt.train/update/table``, split on ``table_reduce``'s ``perfbench:``
line. A part of the model layer, not beside it. None with a program that
names no such scope."""
from perfbench import table_reduce

LAYER = 'model'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return table_reduce.part_ms(run, 'update')
