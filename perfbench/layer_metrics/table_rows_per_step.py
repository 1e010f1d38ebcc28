"""Learned-table rows a trained step reads: the program's
``table.rows_read`` counter (valid table slots of a step, one per row under
exact dedup, summed over the table types; published once a call) over the
window's steps, as the ``table_scan`` executor takes it. What the gather
and the scatter-add scale with; the sweep does not. None where the program
counts none — never 0."""
from perfbench import table_reduce

LAYER = 'model'
UNIT = 'rows/step'
MOVES = 'seeds_per_s'


def read(run):
  return table_reduce.rows_per_step(run)
