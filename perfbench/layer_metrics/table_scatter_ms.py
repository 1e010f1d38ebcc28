"""Device ms per step of the scatter-add of the read rows' gradients into
table-shaped zeros, in the backward pass: self time under ``table/scatter``
inside ``glt.train/fwd_bwd``, split on ``table_reduce``'s ``perfbench:``
line. A part of the model layer, not beside it. None with a program that
names no such scope."""
from perfbench import table_reduce

LAYER = 'model'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return table_reduce.part_ms(run, 'scatter')
