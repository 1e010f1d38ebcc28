"""Device ms per batch of the optimizer step in the PER-BATCH loop: self time
under the program's ``glt.train`` scope (``fwd_bwd``, ``update``) over every op
of the ``step`` executor's traced slice (perfbench/step_reduce.py). None
with a program that has no such scope."""
from perfbench import step_reduce

LAYER = 'model'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return step_reduce.layer_ms(run, 'glt.train')
