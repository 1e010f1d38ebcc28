"""Device ms per batch of sampling in the PER-BATCH loop: self time of every
``XLA Ops`` event of the ``step`` executor's traced slice whose ``op_name`` is
under the program's ``glt.sample`` scope — the ``jit_sample_*`` program's
draws and inducers (perfbench/step_reduce.py; the sub-scopes are on its
line). None with a program that has no such scope."""
from perfbench import step_reduce

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return step_reduce.layer_ms(run, 'glt.sample')
