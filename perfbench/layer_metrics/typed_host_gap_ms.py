"""Device-idle ms per step INSIDE the program's own ``glt.epoch.run`` host
events of the typed cell's traced slice (what ``host_gap_ms`` reads on the
products cells): the gaps of ``trace_reduce.busy`` within each epoch span,
split by innermost span on scope_reduce's line. None with a program whose
spans are not on the profiler's clock."""
from perfbench import scope_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layers(run)['host_gap_ms']
