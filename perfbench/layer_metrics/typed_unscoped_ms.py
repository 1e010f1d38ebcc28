"""The honesty number of the typed chunk: device ms per step of the chunk
program's ops under NONE of ``glt.sample`` / ``glt.collate`` / ``glt.train``
in the traced slice — the scan's own control, key folding, slicing, what
XLA hoisted out of the loop or inserted, and ops whose metadata the
compiler lost. With the three ``typed_*_ms`` it adds up to the chunk
program's busy time. None with a program that has no scope."""
from perfbench import scope_reduce, typed_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  typed_reduce.split(run)
  return scope_reduce.layer_ms(run, scope_reduce.UNSCOPED)
