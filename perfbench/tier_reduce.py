"""The tiered job's split of the traced slice: self time of the ``XLA Ops``
under the scopes a tiered scanned call adds (``docs/observability.md``):
``glt.plan`` — the call's id-only replay of the sampler, in the plan
program and never in a chunk — and ``glt.collate/tier/{lookup,hot,rows}``,
the three parts of the chunk's ``tiered_gather``.

``scope_reduce.layers(run)`` gives the four ``tier_*_ms`` layer readers
their sums over the chunk program (the gather is inside ``glt.collate``; the
plan program is another program, so none of the four holds it); this file
says what the new mechanism costs. ``split(run)`` reduces once per run,
keeps the result in ``run`` and prints one ``perfbench:`` line; with a
program that names none of these scopes (an all-HBM cell, the parent of
PR 41) it finds nothing, prints nothing and returns None.
"""
import collections
import json

from perfbench import scope_reduce

PLAN = 'glt.plan'
TIER = ('glt.collate', 'tier')
PARTS = ('lookup', 'hot', 'rows')
OTHER = 'glt.collate/tier/other'


def tier_scope(path):
  """``glt.plan``, ``glt.collate/tier/<part>`` (``…/other`` for work
  directly under ``tier``) for a ``scope_reduce.scope_path`` under one of
  the tiered job's scopes, else None. An op of the plan's replay carries
  the sampler's own scopes BEHIND ``glt.plan``: it is filed by its first
  ``glt.`` component, which is what ``scope_path`` starts at."""
  if not path:
    return None
  if path[0] == PLAN:
    return PLAN
  if tuple(path[:2]) == TIER:
    part = path[2] if len(path) > 2 else None
    return '/'.join(TIER + (part,)) if part in PARTS else OTHER
  return None


def split(run):
  """``{scope: ms/step}`` over the scopes that occur, or None."""
  if 'tier_reduce' in run:
    return run['tier_reduce']
  a = run['scan']
  out = None
  if a['steps']:
    timed, chips = scope_reduce.timed_of(a)
    acc = collections.defaultdict(float)
    for e, self_us in timed:
      scope = tier_scope(scope_reduce.scope_path(e))
      if scope is not None:
        acc[scope] += self_us / 1e3 / chips / a['steps']
    if acc:
      out = dict(sorted(acc.items()))
      print('perfbench: ' + json.dumps({'tier_reduce': out}), flush=True)
  run['tier_reduce'] = out
  return out


def gather_ms(run):
  """ms/step under ``glt.collate/tier``, its parts added; None where the
  program names none."""
  s = split(run)
  parts = [v for k, v in (s or {}).items() if k != PLAN]
  return sum(parts) if parts else None


def window_share(run, part, whole):
  """``100 * part / whole`` of the window's ``storage.*`` counter deltas
  (``executors/tiered_scan.py`` takes them); None, never 0, where the
  whole is not there or is nothing."""
  c = run['window'].get('tier') or {}
  if not c.get(whole):
    return None
  return 100.0 * c.get(part, 0) / c[whole]
