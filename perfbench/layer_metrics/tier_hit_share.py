"""Share of the window's feature lookups that the HBM hot prefix answered:
the program's own counters ``storage.hot_hits`` / ``storage.lookups``
(valid node slots whose storage row lies below ``hot_rows``, over all valid
node slots; the plan program's sums, published once a call), as the
difference over the measured window. What ``split_ratio`` buys: the rest
crosses the bus. None when nothing was looked up."""
from perfbench import tier_reduce

LAYER = 'feature store'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  return tier_reduce.window_share(run, 'hot_hits', 'lookups')
