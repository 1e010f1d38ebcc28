"""Device ms per step of the model INSIDE the tiered window's own chunk
program: self time under ``glt.train`` (``fwd_bwd``, ``update`` on
scope_reduce's line; ``scan_train_ms``' body over another cell). None with
a program that has no such scope."""
from perfbench import scope_reduce

LAYER = 'model'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layer_ms(run, 'glt.train')
