"""Device ms per step of the model INSIDE the link window's own chunk
program: self time under ``glt.train`` — the forward and backward of the
SAGE layers, the pair loss (``glt.train/pairs``, on ``link_reduce``'s
``perfbench:`` line) and the optimizer (perfbench/scope_reduce.py;
``scan_train_ms``' body over another cell). None with a program that has no
such scope."""
from perfbench import link_reduce, scope_reduce

LAYER = 'model'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  link_reduce.split(run)
  return scope_reduce.layer_ms(run, 'glt.train')
