"""The link job's split of the traced slice: self time of the chunk
program's ``XLA Ops`` under the scopes an edge-seeded step adds
(``docs/observability.md``): ``glt.sample/seeds`` (the epoch order's
positions and the seed-pair gather), ``glt.sample/negative`` (draw,
membership test, compaction), ``glt.sample/union`` (the seed dedup and its
``seed_inverse``) and ``pairs`` inside ``glt.train`` (endpoint gather,
scores, BCE and their backward).

``scope_reduce.layers(run)`` gives the four ``link_*_ms`` layer readers
their sums (it folds the three sampling scopes into ``glt.sample/other``:
a layer is a layer whatever is inside it); this file says where inside the
layer the new mechanism's time went. ``split(run)`` reduces once per run,
keeps the result in ``run`` and prints one ``perfbench:`` line; with a
program that names none of these scopes (a node cell, a program from before
the link scan) it finds nothing, prints nothing and returns None.
"""
import collections
import json
import re

from perfbench import scope_reduce

SAMPLE_SCOPES = ('seeds', 'negative', 'union')
PAIRS = 'pairs'
# the scope is opened inside value_and_grad, which writes it into an
# op_name as jvp(pairs) forward and transpose(jvp(pairs)) backward
_PAIRS = re.compile(r'(?:\w+\()*' + PAIRS + r'\)*$')


def link_scope(path):
  """``glt.sample/negative`` ... or ``glt.train/pairs`` for a
  ``scope_reduce.scope_path`` under one of the link job's scopes, else
  None. ``pairs`` is opened inside ``fwd_bwd``, where autodiff wraps the
  name (``glt.train/fwd_bwd/jvp(pairs)/…``, backward
  ``…/transpose(jvp(pairs))/…``): it is looked for as a component, bare
  or wrapped."""
  if not path:
    return None
  if path[0] == 'glt.sample' and len(path) > 1 and path[1] in SAMPLE_SCOPES:
    return f'glt.sample/{path[1]}'
  if path[0] == 'glt.train' and any(_PAIRS.match(c) for c in path[1:]):
    return f'glt.train/{PAIRS}'
  return None


def split(run):
  """``{scope: ms/step}`` over the four scopes that occur, or None."""
  if 'link_reduce' in run:
    return run['link_reduce']
  a = run['scan']
  out = None
  if a['steps']:
    timed, chips = scope_reduce.timed_of(a)
    acc = collections.defaultdict(float)
    for e, self_us in timed:
      scope = link_scope(scope_reduce.scope_path(e))
      if scope is not None:
        acc[scope] += self_us / 1e3 / chips / a['steps']
    if acc:
      out = dict(sorted(acc.items()))
      print('perfbench: ' + json.dumps({'link_reduce': out}), flush=True)
  run['link_reduce'] = out
  return out


def reject_share(run):
  """Per cent of the window's tested negative candidates that were edges
  of the graph, from the program's ``link.negatives.*`` counters as the
  executor took them over the window; None where nothing was tested."""
  counts = run['window'].get('link') or {}
  tested = counts.get('link.negatives.tested', 0)
  if not tested:
    return None
  return 100.0 * counts.get('link.negatives.rejected', 0) / tested
