"""Device ms per step of the neighbour DRAW inside the window's own chunk
program (slice (a)): self time under ``glt.sample/hop<h>/draw`` summed over
the hops — the row-table gather and the element gather of
``ops.uniform_sample``, the part of ``scan_sample_ms`` that PR 27 cut into
tiles. None with a program that has no such scope.

``draws(run)`` is shared with ``draw_tiles_per_step``: it reduces slice (a)
once per run, keeps the result in ``run``, and prints one ``perfbench:``
line with both numbers hop by hop."""
import collections
import json
import re

from perfbench import scope_reduce, trace_reduce

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'

_TILE = 'tile'
_DRAW = re.compile(r'glt\.sample/(hop\d+)/draw$')


def tile_runs(device):
  """``{hop: executions of the draw's tile body}`` over the ``XLA Ops``
  events whose ``op_name`` has a ``tile`` component under
  ``glt.sample/hop<h>/draw``. Every op of the body runs once per
  execution, so a hop's count is that of its most frequent op instance
  (short ops can be missing from a cut trace; the gathers never are).
  ``{}`` with a program that draws in one piece."""
  seen = collections.Counter()
  for e in device:
    if e['lane'] != trace_reduce.OP_LANE:
      continue
    path = scope_reduce.scope_path(e)
    if len(path) > 3 and path[0] == 'glt.sample' and path[2] == 'draw' \
        and _TILE in path[3:]:
      seen[(path[1], e['chip'], e.get('name', ''))] += 1
  by_hop = collections.defaultdict(dict)
  for (hop, chip, _), n in seen.items():
    by_hop[hop][chip] = max(n, by_hop[hop].get(chip, 0))
  return {hop: sum(chips.values()) / len(chips)
          for hop, chips in sorted(by_hop.items())}


def draws(run):
  """``{'ms': {hop: ms/step} or None, 'tiles': {hop: tile bodies run per
  step} or None}`` of slice (a), once per run."""
  if 'draw_reduce' in run:
    return run['draw_reduce']
  a = run['scan']
  out = {'ms': None, 'tiles': None}
  if a['steps']:
    scopes, _ = scope_reduce.by_scope(a['device'], scope_reduce.CHUNK_STEM,
                                      scope_reduce.timed_of(a))
    ms = {m.group(1): 1e3 * s / a['steps'] for m, s in
          ((_DRAW.match(k), s) for k, s in sorted(scopes.items())) if m}
    tiles = {hop: n / a['steps']
             for hop, n in tile_runs(a['device']).items()}
    out = {'ms': ms or None, 'tiles': tiles or None}
    if ms:
      print('perfbench: ' + json.dumps({'draw_reduce': {
          'draw_ms_by_hop': ms, 'tiles_per_step_by_hop': tiles}}),
            flush=True)
  run['draw_reduce'] = out
  return out


def read(run):
  ms = draws(run)['ms']
  return None if ms is None else sum(ms.values())
