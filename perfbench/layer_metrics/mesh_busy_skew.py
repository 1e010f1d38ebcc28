"""How unevenly the chips are busy: the busiest chip's busy time in the
traced slice over the mean of the chips, less one, in percent. A step ends
when its slowest shard does (every collective is a barrier), so skew is
time the other chips wait inside their collectives."""
from perfbench import mesh_reduce

LAYER = 'device'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  r = mesh_reduce.chips(run)
  if r is None or len(r['busy_ms']) < 2:
    return None
  mean, top = mesh_reduce.over_chips(r['busy_ms'])
  return 100.0 * (top / mean - 1.0) if mean else None
