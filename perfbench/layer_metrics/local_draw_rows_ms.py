"""The part of ``local_draw_ms`` that is the draws' row lookup: self time
of the ops whose ``op_name`` has a ``rows`` component under
``glt.sample/hop<h>/draw`` (``ops/neighbor.py`` ``_local_rows``: global id
to position in ``row_ids`` through the graph's two-level index), summed
over the hops; per hop on the line. A fusion takes its root's name: a
lookup fused into the draw proper reads there. None with a program that
names no part."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return parts.draw_ms(run, (parts.DRAW_ROWS,))
