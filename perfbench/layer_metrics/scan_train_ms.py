"""Device ms per step of the train step INSIDE the window's own chunk
program (slice (a)): self time under the program's ``glt.train`` scope in
``jit_scan_epoch_chunk``, forward, backward and optimizer
(perfbench/scope_reduce.py). None with a program that has no such scope."""
from perfbench import scope_reduce

LAYER = 'model'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layer_ms(run, 'glt.train')
