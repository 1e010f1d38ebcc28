"""Device ms per step of sampling INSIDE the window's own chunk program
(slice (a)): self time of the ``XLA Ops`` events of
``jit_scan_epoch_chunk`` whose ``op_name`` is under the program's
``glt.sample`` scope (perfbench/scope_reduce.py). None with a program that
has no such scope."""
from perfbench import scope_reduce

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layer_ms(run, 'glt.sample')
