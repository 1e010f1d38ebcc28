"""The tiled draw's engagement counter, read off the profiler timeline:
executions per step, summed over the hops, of the tile body of
``ops.uniform_sample`` (the ops under ``glt.sample/hop<h>/draw/.../tile``)
in slice (a). A hop whose frontier is too small to tile adds nothing; a
hop that is tiled adds ``ceil(last valid row / tile rows)``, so the number
falls with the padding the draw no longer gathers for. None with a program
that has no such scope (the parent of PR 27)."""
from perfbench.layer_metrics import scan_draw_ms

LAYER = 'sampling'
UNIT = 'count'
MOVES = 'seeds_per_s'


def read(run):
  tiles = scan_draw_ms.draws(run)['tiles']
  return None if tiles is None else sum(tiles.values())
