"""The mesh cell's reduction of a traced slice: self time per scope PER
CHIP inside the mesh chunk program, its mean and maximum over the chips,
the collectives' share of it, and the chips' busy skew.

``scope_reduce.py`` averages over the chips of a trace, which is right for
one chip and hides what a mesh is measured for: whether the chips are busy
alike, and what part of a step is collectives. This file keeps the chips
apart. ``chips(run)`` reduces once per run, keeps the result in ``run`` and
prints one ``perfbench:`` line (``mesh_reduce``): per layer the mean and
the maximum over chips in ms a step, the collectives' self time split by
the scope that holds them (``glt.sample/hop<h>/exchange``,
``glt.collate/exchange``, ``glt.train/allreduce``; ``docs/observability.md``),
every chip's busy time, and the program's counters of rows and bytes per
step. The eight ``mesh_*`` readers read it.

The chunk program is found by what runs in it, not by its name: the
programs (``XLA Modules`` events) that hold an op under ``glt.train``. With
a program that names no ``exchange`` / ``allreduce`` scope (the parent of
PR 35) the layer sums are still read and ``mesh_exchange_ms`` finds
nothing.
"""
import collections
import json
import re

from perfbench import scope_reduce, trace_reduce

LAYERS = scope_reduce.LAYERS + (scope_reduce.UNSCOPED,)
COLLECTIVES = ('all-to-all', 'all-reduce', 'all-gather', 'reduce-scatter',
               'collective-permute')
EXCHANGE_SCOPES = ('exchange', 'allreduce')
_CONTROL = re.compile(r'(cond|while|body|branch_\d+_fun|cond_fun|body_fun)$')


def is_collective(event):
  """An ``all-to-all`` / ``all-reduce`` ... op, or the ``-start`` /
  ``-done`` half of an asynchronous one."""
  name = trace_reduce._SUFFIX.sub('', event.get('name', ''))
  for suffix in ('-start', '-done'):
    if name.endswith(suffix):
      name = name[:-len(suffix)]
  return name in COLLECTIVES


def exchange_scope(path):
  """``glt.sample/hop1/exchange``, ``glt.collate/exchange``,
  ``glt.train/allreduce`` — the exchange scope an op's path lies under,
  or None. Control flow puts its own components into a path (a bucket
  that may overflow runs under ``lax.cond``: ``glt.sample/cond/
  branch_1_fun/hop1/exchange``); they are dropped."""
  path = [p for p in path if not _CONTROL.match(p)]
  for i, part in enumerate(path[1:], 1):
    if part in EXCHANGE_SCOPES:
      return '/'.join(path[:i + 1])
  return None


def sub_scope(path):
  """``glt.sample/hop1/exchange``, ``glt.collate/cache``,
  ``glt.train/allreduce`` ... — a path cut to its layer's registered
  depth, control-flow components dropped; ``unscoped`` for none."""
  path = [p for p in path if not _CONTROL.match(p)]
  if not path or path[0] not in scope_reduce.LAYERS:
    return scope_reduce.UNSCOPED
  depth = 3 if path[0] == 'glt.sample' and len(path) > 1 and \
      path[1].startswith('hop') else 2
  return '/'.join(path[:depth])


def chunk_programs(device):
  """Per chip the ``(start, end)`` of the programs that hold an op under
  ``glt.train``: the mesh chunk program's executions."""
  progs = collections.defaultdict(list)
  for e in device:
    if e['lane'] == trace_reduce.PROGRAM_LANE:
      progs[e['chip']].append((e['ts'], e['ts'] + e['dur'], e.get('name')))
  names = set()
  for e in device:
    if e['lane'] == trace_reduce.OP_LANE:
      path = scope_reduce.scope_path(e)
      if path and path[0] == 'glt.train':
        names.update(name for lo, hi, name in progs[e['chip']]
                     if _inside(e, lo, hi))
  return {chip: sorted((lo, hi) for lo, hi, name in v if name in names)
          for chip, v in progs.items()}, names


def _inside(event, lo, hi, eps=1e-3):
  return lo - eps <= event['ts'] and event['ts'] + event['dur'] <= hi + eps


def chips(run):
  """``{'layers': {chip: {layer: ms/step}}, 'exchange': {chip: {scope:
  ms/step}}, 'busy_ms': {chip: ms/step}, 'sub_scopes': {scope: ms/step,
  mean over chips}, 'programs': [names]}`` of the traced slice, or None
  where it holds no scoped mesh chunk."""
  if 'mesh_reduce' in run:
    return run['mesh_reduce']
  a = run['scan']
  out = None
  if a['steps']:
    timed, _ = scope_reduce.timed_of(a)
    spans, names = chunk_programs(a['device'])
    per = 1e-3 / a['steps']                      # us -> ms a step
    layers = collections.defaultdict(lambda: dict.fromkeys(LAYERS, 0.0))
    exch = collections.defaultdict(lambda: collections.defaultdict(float))
    subs = collections.defaultdict(float)
    for e, self_us in timed:
      if not any(_inside(e, lo, hi) for lo, hi in spans.get(e['chip'], ())):
        continue
      path = scope_reduce.scope_path(e)
      head = path[0] if path and path[0] in LAYERS else scope_reduce.UNSCOPED
      layers[e['chip']][head] += self_us * per
      subs[sub_scope(path)] += self_us * per
      scope = exchange_scope(path) if path else None
      if scope is not None and is_collective(e):
        exch[e['chip']][scope] += self_us * per
    if layers:
      window = trace_reduce.window_of(a['host'])
      busy = {}
      for chip in layers:
        one = [e for e in a['device'] if e['chip'] == chip]
        busy[chip] = 1e3 * trace_reduce.busy(one, window)[0] / a['steps']
      out = dict(layers={c: dict(v) for c, v in sorted(layers.items())},
                 exchange={c: dict(sorted(exch[c].items()))
                           for c in sorted(layers)},
                 busy_ms=dict(sorted(busy.items())), programs=sorted(names),
                 sub_scopes={k: v / len(layers)
                             for k, v in sorted(subs.items())})
      _say(run, out)
  run['mesh_reduce'] = out
  return out


def over_chips(per_chip):
  """(mean, max) of per-chip numbers."""
  v = list(per_chip.values())
  return sum(v) / len(v), max(v)


def layer_ms(run, layer):
  """Mean over chips of a layer's self time in the chunk, ms a step."""
  r = chips(run)
  if r is None:
    return None
  return over_chips({c: v[layer] for c, v in r['layers'].items()})[0]


def exchange_ms(run):
  """Mean over chips of the collectives' self time under the exchange
  scopes, ms a step; None where the program names none."""
  r = chips(run)
  if r is None or not any(r['exchange'].values()):
    return None
  return over_chips({c: sum(v.values())
                     for c, v in r['exchange'].items()})[0]


def _say(run, out):
  line = {}
  for layer in LAYERS:
    mean, top = over_chips({c: v[layer] for c, v in out['layers'].items()})
    line[layer] = dict(mean=mean, max=top)
  scopes = sorted({s for v in out['exchange'].values() for s in v})
  line['collectives_by_scope'] = {
      s: over_chips({c: v.get(s, 0.0)
                     for c, v in out['exchange'].items()})[0]
      for s in scopes}
  line['sub_scopes_ms'] = out['sub_scopes']
  line['busy_ms_by_chip'] = out['busy_ms']
  line['sum_of_layers'] = sum(line[l]['mean'] for l in LAYERS)
  line['programs'] = out['programs']
  counters = (run.get('window') or {}).get('counters')
  steps = (run.get('window') or {}).get('steps')
  if counters and steps:
    line['counters_per_step'] = {k: v / steps for k, v in counters.items()}
    line['valid_exchange_bytes_a_chip_a_step'] = run['cell'].exchange_bytes(
        counters, steps)
  print('perfbench: ' + json.dumps({'mesh_reduce': line}), flush=True)
