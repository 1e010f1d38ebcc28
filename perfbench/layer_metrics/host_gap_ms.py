"""Device-idle ms per step INSIDE the program's own ``glt.epoch.run`` host
events of slice (a): the gaps of ``trace_reduce.busy`` within each epoch
span, which the program's ``glt.epoch.seeds`` / ``glt.epoch.chunk`` /
``glt.epoch.hook`` / ``glt.epoch.concat`` spans split by innermost span on
scope_reduce's earlier line. None with a program whose spans are not on
the profiler's clock."""
from perfbench import scope_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layers(run)['host_gap_ms']
