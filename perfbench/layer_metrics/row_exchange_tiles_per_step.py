"""The owners' bounded lookup's engagement counter, read off the profiler
timeline as ``draw_tiles_per_step`` reads the draw's: executions per step
and chip of the tile body of ``dist_feature.bounded_lookup`` (the ops whose
``op_name`` has a ``tile`` component under ``glt.collate/exchange``) in the
traced slice's mesh chunk, mean over chips. A received block too narrow to
tile adds nothing; a tiled one adds ``ceil(last valid column / T)``, so
the number follows what the buckets hold, not their capacity. None — never
0 — with a program that has no such scope (the parent of PR 40).

Every op directly in the body runs once per execution, so a loop's count
is that of its most frequent op instance (short ops can be missing from a
cut trace; the row gather never is). An op of a loop nested INSIDE the body
(``indexed_membership``'s halvings) runs once per round and is not counted.
The two capacities of the exchange run under ``lax.cond`` and each has a
loop of its own: the loops are told apart by their ``branch_<i>_fun``
components and their counts add up."""
import collections
import json
import re

from perfbench import mesh_parts_reduce as parts, mesh_reduce, scope_reduce
from perfbench import trace_reduce

LAYER = 'collate'
UNIT = 'count'
MOVES = 'seeds_per_s'

TILE = 'tile'
_BRANCH = re.compile(r'branch_\d+_fun$')


def tile_loop(path):
  """The loop an op's scope path puts it directly in the body of — the
  path's ``branch_<i>_fun`` components, ``()`` outside any ``cond`` — or
  None for an op that is not directly in a tile body under
  ``glt.collate/exchange``."""
  found = parts.part_of(path)
  if not found or found[0] != parts.EXCHANGE or TILE not in path:
    return None
  at = path.index(TILE)
  if any(mesh_reduce._CONTROL.match(p) for p in path[at + 1:]):
    return None                          # a loop nested inside the body
  return tuple(p for p in path[:at] if _BRANCH.match(p))


def tile_runs(device):
  """``{chip: executions of the tile bodies}`` over the ``XLA Ops``
  events; ``{}`` with a program that looks its blocks up in one piece."""
  seen = collections.Counter()
  for e in device:
    if e['lane'] != trace_reduce.OP_LANE:
      continue
    loop = tile_loop(scope_reduce.scope_path(e))
    if loop is not None:
      seen[(e['chip'], loop, e.get('name', ''))] += 1
  by_loop = collections.defaultdict(int)
  for (chip, loop, _), n in seen.items():
    by_loop[chip, loop] = max(n, by_loop[chip, loop])
  by_chip = collections.defaultdict(int)
  for (chip, _), n in by_loop.items():
    by_chip[chip] += n
  return dict(sorted(by_chip.items()))


def read(run):
  if 'row_exchange_tiles' not in run:
    a = run['scan']
    runs = tile_runs(a['device']) if a['steps'] else {}
    out = None
    if runs:
      per_chip = {chip: n / a['steps'] for chip, n in runs.items()}
      out = mesh_reduce.over_chips(per_chip)[0]
      print('perfbench: ' + json.dumps({'row_exchange_tiles': {
          'tiles_per_step_by_chip': per_chip}}), flush=True)
    run['row_exchange_tiles'] = out
  return run['row_exchange_tiles']
