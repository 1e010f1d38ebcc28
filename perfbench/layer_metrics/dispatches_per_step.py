"""Program launches the epoch executor made per training step, counted by
the program's own ``DispatchCounter`` over the measured window."""
LAYER = 'epoch executors'
UNIT = 'count'
MOVES = 'seeds_per_s'


def read(run):
  w = run['window']
  return w['dispatches'] / w['steps'] if w['steps'] else None
