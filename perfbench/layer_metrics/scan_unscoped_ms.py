"""The honesty number: device ms per step of the chunk program's ops under
NONE of ``glt.sample`` / ``glt.collate`` / ``glt.train`` in slice (a) — the
loop's own overhead, slicing and key folding, what XLA hoisted out of the
loop, and any fusion it formed across a layer boundary (a fusion is
attributed by its root's ``op_name``). With the three layers it adds up
to the chunk program's busy time. None with a program that has no scope."""
from perfbench import scope_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layer_ms(run, scope_reduce.UNSCOPED)
