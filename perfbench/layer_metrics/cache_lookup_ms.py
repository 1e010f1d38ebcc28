"""Device ms per step of the hot cache's lookup: self time under
``glt.collate/cache/lookup`` — ``indexed_membership`` of the node buffer's
ids over the cached ids; the hit-row gather (``/rows``) is beside it on the
``mesh_parts_reduce`` line. None with a program that names no part."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return parts.ms(run, parts.CACHE, ('lookup',))
