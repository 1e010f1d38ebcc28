"""Rows a call read SYNCHRONOUSLY on the dispatch thread because the
staging worker had failed or was too slow (``ChunkStager.take``'s degraded
path): the program's counter ``storage.prefetch_miss`` over the measured
window, per call. 0 is a healthy pipeline: a count, so 0 is a reading.
None with a program that publishes no ``storage.*`` counter."""
LAYER = 'feature store'
UNIT = 'count'
MOVES = 'seeds_per_s'


def read(run):
  c = run['window'].get('tier')
  if c is None or not run['window'].get('calls'):
    return None
  return c.get('prefetch_miss', 0) / run['window']['calls']
