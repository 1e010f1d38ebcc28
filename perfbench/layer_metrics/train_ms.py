"""Device ms per step of the train-step program (jit_train_step) in the per-batch slice
(slice (b)): the per-batch programs at the cell's shapes, not the scanned
body, which is one program with no named scopes today."""
from perfbench import trace_reduce

LAYER = 'model'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return trace_reduce.program_ms_per_step(run['step'], 'jit_train_step')
