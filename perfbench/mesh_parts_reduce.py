"""The mesh step's largest scopes, opened (PR 39): self time per PART of
the miss-only row exchange, of the cache split and of the shard-local
draw inside the mesh chunk program, per chip, and the exchange's fill
ratio from the program's own counters.

``mesh_reduce.py`` cuts a path to its layer's registered depth
(``glt.collate/exchange``, ``glt.sample/hop<h>/draw``); since PR 39 the
program names what runs inside those (``docs/observability.md``):

  ``glt.collate/exchange/{dedup,route,pack,wire,lookup,rows,unpack,fanout}``
  ``glt.collate/cache/{lookup,rows}``
  ``glt.sample/hop<h>/draw/.../rows``

This file reads one level further down. It takes the self times and the
chunk program's executions from ``scope_reduce.timed_of`` /
``mesh_reduce.chunk_programs`` (one reduction a run), drops control-flow
and ``jit(...)`` / ``jvp(...)`` components from a path, keeps the chips
apart and returns the mean over chips as ``mesh_reduce`` does.
``parts(run)`` reduces once per run, keeps the result in ``run`` and prints
ONE ``perfbench:`` line (``mesh_parts_reduce``): every part in ms a step,
mean and maximum over chips, per hop for the draw. A fusion carries its
root's name, so a part's figure is what XLA rooted inside it; what it
rooted under ``glt.collate/exchange`` and outside all eight parts is
``unsplit``, and the parts and ``unsplit`` add up to ``whole``.

With a program that names no part (the parent of PR 39, the recorded PR 35
trace, any one-chip cell) ``parts`` is None and every reader returns None,
never 0.
"""
import collections
import json
import re

from perfbench import mesh_reduce, scope_reduce

EXCHANGE = 'glt.collate/exchange'
CACHE = 'glt.collate/cache'
EXCHANGE_PARTS = ('dedup', 'route', 'pack', 'wire', 'lookup', 'rows',
                  'unpack', 'fanout')
CACHE_PARTS = ('lookup', 'rows')
DRAW_ROWS = 'rows'
WHOLE, UNSPLIT = 'whole', 'unsplit'
_WRAPPED = re.compile(r'\w+\(.*\)$')      # jit(f), jvp(f), transpose(jvp(f))
_HOP = re.compile(r'hop\d+$')


def clean(path):
  """A scope path without what control flow and function transforms put
  into it: ``glt.collate/exchange/cond/branch_0_fun/pack/jit(f)/…`` reads
  ``glt.collate/exchange/pack/…``."""
  return tuple(p for p in path
               if not mesh_reduce._CONTROL.match(p) and not _WRAPPED.match(p))


def part_of(path):
  """``(group, part)`` of a scope path (``scope_reduce.scope_path``'s
  value): group ``glt.collate/exchange``, ``glt.collate/cache`` or
  ``glt.sample/hop<h>/draw``, part one of the group's registered parts or
  ``unsplit``; None for a path under none of the three."""
  path = clean(path)
  if len(path) >= 2 and path[0] == 'glt.collate':
    for group, names in ((EXCHANGE, EXCHANGE_PARTS), (CACHE, CACHE_PARTS)):
      if '/'.join(path[:2]) == group:
        named = len(path) > 2 and path[2] in names
        return group, path[2] if named else UNSPLIT
  if len(path) >= 3 and path[0] == 'glt.sample' and _HOP.match(path[1]) \
      and path[2] == 'draw':
    return '/'.join(path[:3]), \
        DRAW_ROWS if DRAW_ROWS in path[3:] else UNSPLIT
  return None


def parts(run):
  """``{group: {part or 'whole' or 'unsplit': {chip: ms/step}}}`` of the
  traced slice's mesh chunk, or None where the program names no part."""
  if 'mesh_parts_reduce' in run:
    return run['mesh_parts_reduce']
  a = run['scan']
  out = None
  if a['steps']:
    timed, _ = scope_reduce.timed_of(a)
    spans, _ = mesh_reduce.chunk_programs(a['device'])
    per = 1e-3 / a['steps']                      # us -> ms a step
    acc = collections.defaultdict(lambda: collections.defaultdict(float))
    chips, named = set(), False
    for e, self_us in timed:
      if not any(mesh_reduce._inside(e, lo, hi)
                 for lo, hi in spans.get(e['chip'], ())):
        continue
      chips.add(e['chip'])
      found = part_of(scope_reduce.scope_path(e))
      if found is None:
        continue
      group, part = found
      named = named or part != UNSPLIT
      acc[group, part][e['chip']] += self_us * per
      acc[group, WHOLE][e['chip']] += self_us * per
    if named:
      out = collections.defaultdict(dict)
      for (group, part), by_chip in sorted(acc.items()):
        out[group][part] = {c: by_chip.get(c, 0.0) for c in sorted(chips)}
      out = dict(out)
      _say(out)
  run['mesh_parts_reduce'] = out
  return out


def ms(run, group, names):
  """Mean over chips of the summed self time of ``names`` (parts, or
  ``whole`` / ``unsplit``) of one group, ms a step; None where the
  program names no part or the group did not run."""
  r = parts(run)
  if r is None or group not in r:
    return None
  return sum(mesh_reduce.over_chips(r[group][n])[0]
             for n in names if n in r[group])


def draw_ms(run, names):
  """The same over every hop's draw: ``glt.sample/hop<h>/draw`` summed."""
  r = parts(run)
  hops = [g for g in (r or ()) if g.startswith('glt.sample/')]
  if not hops:
    return None
  return sum(ms(run, g, names) for g in hops)


def fill_share(run):
  """Valid unique misses over the request slots the row exchange moved in
  the window, in %: ``dist_feature.unique_misses`` (window delta, every
  shard and step) / (steps × partitions × the gauge
  ``dist_feature.exchange_slots``, read from this process's registry).
  None where the program sets no such gauge or counted nothing."""
  import graphlearn_tpu as glt
  slots = glt.metrics.snapshot()['gauges'].get('dist_feature.exchange_slots')
  win = run['window']
  misses = (win.get('counters') or {}).get('dist_feature.unique_misses')
  parts_ = getattr(run['cell'], 'parts', None)
  if not slots or not misses or not win.get('steps') or not parts_:
    return None
  return 100.0 * misses / (win['steps'] * parts_ * slots)


def _say(out):
  both = lambda by_chip: dict(zip(('mean', 'max'),
                                  mesh_reduce.over_chips(by_chip)))
  line = {group: {part: both(by_chip) for part, by_chip in by_part.items()}
          for group, by_part in out.items()}
  print('perfbench: ' + json.dumps({'mesh_parts_reduce': line}), flush=True)
