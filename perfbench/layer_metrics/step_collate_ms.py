"""Device ms per batch of collate in the PER-BATCH loop: self time under the
program's ``glt.collate`` scope over every op of the ``step`` executor's traced
slice — the ``jit_collate_batch`` program's row gather, without the relayout
copy at its boundary, which carries no scope and reads as unscoped
(perfbench/step_reduce.py). None with a program that has no such scope."""
from perfbench import step_reduce

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return step_reduce.layer_ms(run, 'glt.collate')
