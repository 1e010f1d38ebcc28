"""The typed split of the traced slice: self time of the chunk program's
``XLA Ops`` by edge type and by node type, read from the scopes the typed
hop loop and the typed collate name (``docs/observability.md``):
``glt.sample/hop<h>/<src>__<rel>__<dst>/draw`` and ``/induce``,
``glt.sample/hop<h>/merge``, ``glt.collate/<ntype>``.

``scope_reduce.layers(run)`` gives the four ``typed_*_ms`` readers their
layer sums (a layer is a layer whatever is inside it); this file only adds
the line that says where inside the layer the time went. ``split(run)``
reduces once per run, keeps the result in ``run`` and prints one
``perfbench:`` line; with a program that names no typed scope (the parent
of PR 30, a homogeneous cell) it finds nothing and prints nothing.
``typed_draw_ms`` sums the split's draws; ``tiles(run)`` counts what the
tiled draw (PR 27) ran under the typed draw scopes, for
``typed_draw_tiles_per_step``.
"""
import collections
import json
import re

from perfbench import scope_reduce, trace_reduce

_HOP = re.compile(r'hop\d+$')
_PARTS = ('draw', 'induce')


def typed_scope(path, ntypes=()):
  """``(kind, key)`` of a ``scope_reduce.scope_path``: ``('relation',
  'hop1/paper__cites__paper/draw')``, ``('merge', 'hop1')``, ``('ntype',
  'paper')`` for a node type among ``ntypes`` — or None for an op under
  no typed scope."""
  if not path:
    return None
  if path[0] == 'glt.sample' and len(path) > 2 and _HOP.match(path[1]):
    if path[2] == 'merge':
      return 'merge', path[1]
    if len(path) > 3 and '__' in path[2] and path[3] in _PARTS:
      return 'relation', '/'.join(path[1:4])
  if path[0] == 'glt.collate' and len(path) > 1 and path[1] in ntypes:
    return 'ntype', path[1]
  return None


def split(run):
  """``{'relation': {hop/relation/part: ms/step}, 'merge': {hop: ms/step},
  'ntype': {node type: ms/step}}`` of the traced slice's chunk program, or
  None where no op carries a typed scope."""
  if 'typed_reduce' in run:
    return run['typed_reduce']
  a = run['scan']
  out = None
  if a['steps']:
    timed, chips = scope_reduce.timed_of(a)
    ntypes = tuple(getattr(run['cell'], 'ntypes', ()))
    acc = collections.defaultdict(lambda: collections.defaultdict(float))
    for e, self_us in timed:
      kind = typed_scope(scope_reduce.scope_path(e), ntypes)
      if kind is not None:
        acc[kind[0]][kind[1]] += self_us / 1e3 / chips / a['steps']
    if acc:
      out = {k: dict(sorted(v.items())) for k, v in acc.items()}
      by_rel = collections.defaultdict(float)
      for key, ms in out.get('relation', {}).items():
        _, rel, part = key.split('/')
        by_rel[f'{rel}/{part}'] += ms
      print('perfbench: ' + json.dumps({'typed_reduce': dict(
          out, relation_over_hops=dict(sorted(by_rel.items())))}),
            flush=True)
  run['typed_reduce'] = out
  return out


def tiles(run):
  """``{hop/relation: executions per step of the draw's tile body}`` over
  the ``XLA Ops`` events with a ``tile`` component under
  ``glt.sample/hop<h>/<relation>/draw`` — per draw the count of its most
  frequent op instance, as ``scan_draw_ms.tile_runs`` counts the
  homogeneous draws. None where no typed draw tiles."""
  if 'typed_tiles' in run:
    return run['typed_tiles']
  a = run['scan']
  seen = collections.Counter()
  for e in a['device'] if a['steps'] else ():
    if e['lane'] != trace_reduce.OP_LANE:
      continue
    path = scope_reduce.scope_path(e)
    kind = typed_scope(path)
    if kind and kind[0] == 'relation' and path[3] == 'draw' \
        and 'tile' in path[4:]:
      seen[('/'.join(path[1:3]), e['chip'], e.get('name', ''))] += 1
  by_draw = collections.defaultdict(dict)
  for (draw, chip, _), n in seen.items():
    by_draw[draw][chip] = max(n, by_draw[draw].get(chip, 0))
  out = {draw: sum(chips.values()) / len(chips) / a['steps']
         for draw, chips in sorted(by_draw.items())} or None
  if out:
    print('perfbench: ' + json.dumps({'typed_tiles_per_step': out}),
          flush=True)
  run['typed_tiles'] = out
  return out
