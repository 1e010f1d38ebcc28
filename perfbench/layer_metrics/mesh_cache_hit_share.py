"""Share of the feature lookups of the window that the replicated hot cache
answered on the chip that asked: the program's own counters
``dist_feature.hits`` / ``dist_feature.lookups``, published once an epoch,
as the difference over the measured window (every shard, every step)."""
LAYER = 'collate'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  c = run['window'].get('counters') or {}
  lookups = c.get('dist_feature.lookups')
  if not lookups:
    return None
  return 100.0 * c['dist_feature.hits'] / lookups
