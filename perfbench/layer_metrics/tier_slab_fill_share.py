"""Share of the uploaded slab rows that held a planned row, over the
window: the program's counters ``storage.planned_rows`` (sorted unique
non-hot rows a chunk, summed) / ``storage.slab_cap_rows`` (the pow2-padded
capacity uploaded for them). The rest is padding the bus carried and the
chunk's membership search ran over. None where nothing was staged."""
from perfbench import tier_reduce

LAYER = 'feature store'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  return tier_reduce.window_share(run, 'planned_rows', 'slab_cap_rows')
