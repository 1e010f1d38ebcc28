"""Device ms per step the mesh chunk program spends under none of the three
layer scopes — loop control, slicing, what XLA hoisted or inserted, ops
whose metadata the compiler lost: the honesty number. Per chip, mean over
the chips (perfbench/mesh_reduce.py); with its three siblings it adds up
to a chip's busy time."""
from perfbench import mesh_reduce, scope_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return mesh_reduce.layer_ms(run, scope_reduce.UNSCOPED)
