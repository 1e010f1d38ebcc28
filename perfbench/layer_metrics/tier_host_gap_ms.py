"""Device-idle ms per step INSIDE the program's own ``glt.epoch.run`` host
events of the tiered cell's traced slice (what ``host_gap_ms`` reads on the
products cells): the gaps of ``trace_reduce.busy`` within each epoch span,
split by the innermost of ``glt.epoch.plan`` / ``glt.epoch.stage_wait``
(inside it the staging worker's ``glt.storage.stage``, where the worker is
still at the chunk the dispatch thread waits for) / ``glt.epoch.upload`` /
``glt.epoch.chunk`` (and ``epoch.stage``, ``epoch.publish``,
``epoch.concat``) on scope_reduce's line. The first cell
in which the host can hold the chip back: the wait for a call's first slab
is here. None with a program whose spans are not on the profiler's
clock."""
from perfbench import scope_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layers(run)['host_gap_ms']
