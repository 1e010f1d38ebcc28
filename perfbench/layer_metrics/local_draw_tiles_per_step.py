"""The shard-local draws' tile loop, engaged: executions per step and chip
of the tile body of a shard-local draw (``ops.uniform_sample_local`` /
``weighted_sample_local``, which run ``ops.neighbor.draw_tiled`` over the
received request block) — the ``XLA Ops`` events directly in
``glt.sample/hop<h>/draw/jit(<draw>_sample_local)/while/body/tile`` in the
traced slice — counted as ``row_exchange_tiles_per_step`` counts the
owners' (``tile_runs``: a loop's count is its most frequent op instance's,
since short ops can be missing from a cut trace; an op of a loop nested
INSIDE the body, the row lookup's halvings or the weighted draw's
bisection, runs once a round and is not counted; chips are averaged).
Each hop's exchange runs under ``lax.cond`` with a loop in each branch:
the loops are told apart by hop and ``branch_<i>_fun`` and add up, over
the branches and over the hops (per hop on the ``local_draw_tiles``
line).

A received block adds ``ceil(last valid column / T)``, ``T =
ops.neighbor.draw_tile_rows(cap)`` (512 of hop 2's 30,720 columns in
``sage-papers.mesh-exact``, 256 of hop 1's 4,480; hop 0's 256 columns are
drawn in one piece and add nothing), so the number follows what the
buckets hold, not their capacity. None — never 0 — with a program that
draws its blocks in one piece; the one-chip draw's tiles
(``ops.uniform_sample``, ``draw_tiles_per_step``'s) are not counted."""
import json
import re

from perfbench import mesh_parts_reduce as parts, mesh_reduce, scope_reduce
from perfbench import trace_reduce
from perfbench.layer_metrics import row_exchange_tiles_per_step as exchange

LAYER = 'sampling'
UNIT = 'count'
MOVES = 'seeds_per_s'

TILE = 'tile'
_LOCAL = re.compile(r'jit\(\w+_sample_local\)$')


def tile_loop(path):
  """The loop an op's scope path puts it directly in the body of —
  ``(glt.sample/hop<h>/draw, *branch_<i>_fun components)`` — or None for
  an op that is not directly in a shard-local draw's tile body."""
  found = parts.part_of(path)
  if not found or not found[0].startswith('glt.sample/') or TILE not in path:
    return None
  at = path.index(TILE)
  if not any(_LOCAL.match(p) for p in path[:at]):
    return None                          # the one-chip draw's tiles
  if any(mesh_reduce._CONTROL.match(p) for p in path[at + 1:]):
    return None                          # a loop nested inside the body
  return (found[0],) + tuple(p for p in path[:at]
                             if exchange._BRANCH.match(p))


def tile_runs(device, hop=None):
  """``{chip: executions of the tile bodies}``, of every hop or of the
  draw group ``hop`` alone; ``{}`` with a program that draws in one
  piece."""
  def loop_of(path):
    loop = tile_loop(path)
    return loop if loop and hop in (None, loop[0]) else None
  return exchange.tile_runs(device, loop_of)


def read(run):
  a = run['scan']
  runs = tile_runs(a['device']) if a['steps'] else {}
  if not runs:
    return None
  per_step = lambda by_chip: mesh_reduce.over_chips(by_chip)[0] / a['steps']
  hops = sorted({loop[0] for loop in (
      tile_loop(scope_reduce.scope_path(e)) for e in a['device']
      if e['lane'] == trace_reduce.OP_LANE) if loop})
  print('perfbench: ' + json.dumps({'local_draw_tiles': {
      'tiles_per_step_by_hop': {h: per_step(tile_runs(a['device'], h))
                                for h in hops},
      'tiles_per_step_by_chip': {c: n / a['steps']
                                 for c, n in runs.items()}}}), flush=True)
  return per_step(runs)
