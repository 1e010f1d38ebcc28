"""The learned tables' split of the traced slice: self time of the chunk
program's ``XLA Ops`` under the three scopes a step over learned node
tables adds (``docs/observability.md``): ``table/gather`` inside
``glt.train/fwd_bwd`` (the rows read by node id), ``table/scatter`` beside
it (the scatter-add of their gradients, in the backward pass) and
``glt.train/update/table`` (the optimizer over the table leaves — dense
Adam's sweep over every row).

``scope_reduce.layers(run)`` files all three under ``glt.train``; this file
says how much of the model layer the tables are. ``split(run)`` reduces
once per run, keeps the result in ``run`` and prints one ``perfbench:``
line; with a program that names none of these scopes (a cell without
tables, a program from before them) it finds nothing, prints nothing and
returns None.
"""
import collections
import json
import re

from perfbench import scope_reduce

TABLE = 'table'
PARTS = ('gather', 'scatter', 'update')
# autodiff wraps a scope opened inside value_and_grad: jvp(table) forward,
# and the transpose's own name stack before it in the backward pass
_TABLE = re.compile(r'(?:[\w.]+\()*' + TABLE + r'\)*$')


def table_part(path):
  """``'gather'``, ``'scatter'`` or ``'update'`` for a
  ``scope_reduce.scope_path`` under one of the tables' scopes, else None:
  ``glt.train/update/table/…``, or a ``table`` component (bare or
  wrapped) followed by ``gather`` / ``scatter`` under ``glt.train``."""
  if not path or path[0] != 'glt.train':
    return None
  if len(path) > 2 and path[1] == 'update' and path[2] == TABLE:
    return 'update'
  for i, c in enumerate(path[1:-1], 1):
    if _TABLE.match(c) and path[i + 1] in PARTS[:2]:
      return path[i + 1]
  return None


def split(run):
  """``{part: ms/step}`` over the parts that occur, or None."""
  if 'table_reduce' in run:
    return run['table_reduce']
  a = run['scan']
  out = None
  if a['steps']:
    timed, chips = scope_reduce.timed_of(a)
    acc = collections.defaultdict(float)
    for e, self_us in timed:
      part = table_part(scope_reduce.scope_path(e))
      if part is not None:
        acc[part] += self_us / 1e3 / chips / a['steps']
    if acc:
      out = dict(sorted(acc.items()))
      print('perfbench: ' + json.dumps({'table_reduce': out}), flush=True)
  run['table_reduce'] = out
  return out


def part_ms(run, part):
  s = split(run)
  return None if s is None else s.get(part)


def rows_per_step(run):
  """Valid table slots a trained step read, from the program's
  ``table.rows_read`` counter as the executor took it over the window;
  None where the program counts none."""
  win = run['window']
  n = (win.get('table') or {}).get('table.rows_read')
  if not n or not win.get('steps'):
    return None
  return n / win['steps']
