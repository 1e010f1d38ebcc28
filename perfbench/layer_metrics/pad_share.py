"""The work static shapes waste: 1 - valid node rows / node-buffer rows,
mean over the batches the executor counts on (``valid_counts()``: under
``scan`` the first chunk's replayed batches, the window's own)."""
LAYER = 'capacity'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  c = run['counts']
  if not c['nodes']:
    return None
  return 100.0 * (1.0 - sum(c['nodes']) / c['buffer_rows'])
