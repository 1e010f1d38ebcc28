"""Share of the traced slice of the cell's own executor in which no
operation ran on the device: 1 - union of the device-op intervals / the
slice's wall (from the harness's first annotation to its last)."""
LAYER = 'device'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  s = run['scan']
  if not s['window_s'] or not s['busy_s']:
    return None
  return 100.0 * (1.0 - s['busy_s'] / s['window_s'])
