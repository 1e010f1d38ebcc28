"""The tiled draw's engagement counter on typed frontiers: executions per
step, summed over the typed draws, of the tile body of
``ops.uniform_sample`` (the ops under
``glt.sample/hop<h>/<relation>/draw/.../tile``) in the traced slice. A draw
whose frontier is too small to tile adds nothing; the count per draw is on
``typed_reduce.tiles``' line. None with a program where no typed draw
tiles."""
from perfbench import typed_reduce

LAYER = 'sampling'
UNIT = 'count'
MOVES = 'seeds_per_s'


def read(run):
  tiles = typed_reduce.tiles(run)
  return None if tiles is None else sum(tiles.values())
