"""The per-batch loop's honesty number: device ms per batch of the traced
slice's ops under NONE of ``glt.sample`` / ``glt.collate`` / ``glt.train`` —
what a loop of separate programs pays that the scanned chunk does not (the
relayout copies at each program's boundary, key folding, the overflow
flag's small programs) and metadata the compiler lost. With the three layers
it adds up to the slice's busy time (perfbench/step_reduce.py). None with a
program that has no scope."""
from perfbench import scope_reduce, step_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return step_reduce.layer_ms(run, scope_reduce.UNSCOPED)
