"""From a slice's device events to SELF TIME PER SCOPE: the layer clock
inside one program.

The program names everything it puts on a profiler timeline ``glt.<...>``
(PR 26; ``docs/observability.md``): device side, ``jax.named_scope`` in the
layers — ``glt.sample`` (with ``hop<h>/draw`` and ``hop<h>/induce`` inside),
``glt.collate``, ``glt.train`` (``fwd_bwd``, ``update``) — which XLA keeps
in every instruction's ``op_name``; host side, the program's own spans
(``glt.epoch.run``, ``glt.epoch.chunk``, ``glt.epoch.seeds``,
``glt.epoch.concat``, ``glt.epoch.hook``). On a v5e each ``XLA Ops`` event
of the ``trace.json.gz`` carries that ``op_name`` in ``args.tf_op`` (a
fusion's is its root's), so this file needs nothing from the program but
the names. An event without one — ``while`` itself, copies XLA added,
anything it hoisted out of the loop — is *unscoped*.

Self time is an event's ``dur`` less the ``dur`` of the events nested
directly in it on the same lane, so a ``while`` / ``conditional`` / ``call``
counts only its own overhead and the self times of a lane add up to the
union of its busy intervals (``trace_reduce.op_seconds`` sums a wrapper and
its children, which is why ``breakdown.device_ops`` is led by ``while``).

``layers(run)`` is what the six ``scan_*`` / ``host_gap_ms`` readers share.
It reduces slice (a) — the window's own chunk program — once per run, keeps
the result in ``run``, and prints one ``perfbench:`` line with the sub-scope
split, ``host_gap_ms`` by innermost span, what tracing cost, and the
cross-check: the same reduction over slice (b), where each scope *is* one
program, beside the program-lane ``sample_ms`` / ``collate_ms`` /
``train_ms``. With a program that has no ``glt.`` scope or span (the parent
of PR 26) every reader finds nothing and returns ``None``.
"""
import bisect
import collections
import json
import re

from perfbench import trace_reduce

PREFIX = 'glt.'
LAYERS = ('glt.sample', 'glt.collate', 'glt.train')
UNSCOPED = 'unscoped'
CHUNK_STEM = 'jit_scan_epoch_chunk'
EPOCH_SPAN = 'glt.epoch.run'
_HOP = re.compile(r'hop\d+$')
_SUBS = {'glt.sample': ('draw', 'induce'), 'glt.train': ('fwd_bwd', 'update')}
_EPS_US = 1e-3      # a child may outlast its parent by the trace's rounding


def scope_path(event):
  """The ``glt.`` part of an event's ``op_name`` as a tuple of components,
  from the first ``glt.<layer>`` on; ``()`` for an event under none."""
  parts = (event.get('args') or {}).get('tf_op', '').rstrip(':').split('/')
  for i, p in enumerate(parts):
    if p.startswith(PREFIX):
      return tuple(parts[i:])
  return ()


def sub_scope(path):
  """``glt.sample/hop1/induce``, ``glt.train/fwd_bwd`` ... — the layer's
  registered sub-scope, or ``<layer>/other`` for work directly under it."""
  layer = path[0]
  if layer == 'glt.sample' and len(path) > 2 and _HOP.match(path[1]) \
      and path[2] in _SUBS[layer]:
    return '/'.join(path[:3])
  if layer == 'glt.train' and len(path) > 1 and path[1] in _SUBS[layer]:
    return '/'.join(path[:2])
  return layer + '/other'


def self_times(device):
  """``([(event, self_us)], chips)`` over the ``XLA Ops`` lane of every
  chip in the trace: an event's ``dur`` less its direct children's."""
  chips = collections.defaultdict(list)
  for e in device:
    if e['lane'] == trace_reduce.OP_LANE:
      chips[e['chip']].append(e)
  out = []
  for events in chips.values():
    events.sort(key=lambda e: (e['ts'], -e['dur']))
    open_ = []                       # (end_us, index into out), innermost last
    for e in events:
      while open_ and open_[-1][0] <= e['ts'] + _EPS_US:
        open_.pop()
      if open_:
        parent, self_us = out[open_[-1][1]]
        out[open_[-1][1]] = (parent, self_us - e['dur'])
      open_.append((e['ts'] + e['dur'], len(out)))
      out.append((e, e['dur']))
  return out, max(1, len(chips))


def by_scope(device, stem=None, timed=None):
  """``({scope: seconds}, {op class: seconds of the unscoped})`` — self
  time keyed by layer sub-scope (``sub_scope``) or ``unscoped``, over the
  ops that ran inside the programs whose name contains ``stem`` (every
  op when ``stem`` is None); averaged over the chips in the trace.
  ``timed`` is ``self_times(device)`` where the caller already has it."""
  timed, chips = timed or self_times(device)
  if stem is not None:
    iv = sorted((e['chip'], e['ts'], e['ts'] + e['dur']) for e in device
                if e['lane'] == trace_reduce.PROGRAM_LANE
                and stem in e.get('name', ''))
    starts = [(c, lo) for c, lo, _ in iv]

    def inside(e):
      i = bisect.bisect_right(starts, (e['chip'], e['ts'] + _EPS_US)) - 1
      return i >= 0 and iv[i][0] == e['chip'] and \
          e['ts'] + e['dur'] <= iv[i][2] + _EPS_US
    timed = [(e, s) for e, s in timed if inside(e)]
  scopes = collections.defaultdict(float)
  loose = collections.defaultdict(float)
  for e, self_us in timed:
    path = scope_path(e)
    if path:
      scopes[sub_scope(path)] += self_us / 1e6 / chips
    else:
      scopes[UNSCOPED] += self_us / 1e6 / chips
      loose[trace_reduce._SUFFIX.sub('', e.get('name', ''))] += \
          self_us / 1e6 / chips
  return dict(scopes), dict(sorted(loose.items(), key=lambda kv: -kv[1]))


def by_layer(scopes):
  """``{glt.sample: s, glt.collate: s, glt.train: s, unscoped: s}`` from
  ``by_scope``'s first value; None when no op carried a ``glt.`` scope."""
  if not any(k != UNSCOPED for k in scopes):
    return None
  out = {layer: 0.0 for layer in LAYERS + (UNSCOPED,)}
  for k, s in scopes.items():
    head = k.split('/')[0]
    out[head if head in out else UNSCOPED] += s
  return out


def host_gaps(device, host):
  """``{innermost glt. span: idle seconds}`` of the first chip inside the
  program's own ``glt.epoch.run`` host events; None when the trace holds
  none (a program from before PR 26)."""
  runs = [e for e in host if e.get('name') == EPOCH_SPAN]
  if not runs:
    return None
  out = collections.defaultdict(float)
  for r in runs:
    _, _, gaps = trace_reduce.busy(device, (r['ts'], r['ts'] + r['dur']))
    for label, s in trace_reduce.label_gaps(gaps, host, top=len(gaps),
                                            prefix=PREFIX):
      out[label] += s
  return dict(out)


def layers(run):
  """Slice (a) by layer, once per run: ``{'ms': {glt.sample, glt.collate,
  glt.train, unscoped} ms/step or None, 'host_gap_ms': ms/step or None}``.
  The first call prints the ``perfbench:`` line described above."""
  if 'scope_reduce' in run:
    return run['scope_reduce']
  a, b = run['scan'], run['step']
  out = {'ms': None, 'host_gap_ms': None}
  line = {}
  if a['steps']:
    timed_a = self_times(a['device'])
    scopes, loose = by_scope(a['device'], CHUNK_STEM, timed_a)
    per_step = lambda s: 1e3 * s / a['steps']
    layer_s = by_layer(scopes)
    if layer_s is not None:
      out['ms'] = {k: per_step(s) for k, s in layer_s.items()}
      everything, _ = by_scope(a['device'], None, timed_a)
      line['scan_ms_per_step'] = dict(
          out['ms'], sum=sum(out['ms'].values()),
          busy=per_step(a['busy_s']),
          other_programs=per_step(sum(everything.values()) -
                                  sum(scopes.values())))
      line['scan_sub_scopes_ms'] = {k: per_step(s)
                                    for k, s in sorted(scopes.items())}
      line['scan_unscoped_ops_ms'] = {k: per_step(s)
                                      for k, s in list(loose.items())[:8]}
    gaps = host_gaps(a['device'], a['host'])
    if gaps is not None:
      out['host_gap_ms'] = per_step(sum(gaps.values()))
      line['host_gap_ms_by_span'] = {k: per_step(s)
                                     for k, s in gaps.items()}
    if a['window_s'] and run['window'].get('wall_s'):
      line['tracing_on_steps_per_s'] = a['steps'] / a['window_s']
      line['tracing_off_steps_per_s'] = (run['window']['steps'] /
                                         run['window']['wall_s'])
  if b['steps'] and b['device'] is not a['device']:
    timed_b = self_times(b['device'])
    layer_b = by_layer(by_scope(b['device'], None, timed_b)[0])
    if layer_b is not None:
      # scope_ms against program_ms proves the names; all_ops_ms (every
      # op inside that program, scoped or not) against program_ms proves
      # the reducer; what separates them is metadata the compiler lost
      check = {}
      for layer, stem in (('glt.sample', 'jit_sample_'),
                          ('glt.collate', 'jit_collate_batch'),
                          ('glt.train', 'jit_train_step')):
        program = trace_reduce.program_ms_per_step(b, stem)
        scope = 1e3 * layer_b[layer] / b['steps']
        all_ops = 1e3 * sum(
            by_scope(b['device'], stem, timed_b)[0].values())
        check[layer] = {'scope_ms': scope, 'program_ms': program,
                        'all_ops_ms': all_ops / b['steps'],
                        'ratio': scope / program if program else None}
      line['cross_check_slice_b'] = check
  if line:
    print('perfbench: ' + json.dumps({'scope_reduce': line}), flush=True)
  run['scope_reduce'] = out
  return out


def layer_ms(run, layer):
  ms = layers(run)['ms']
  return None if ms is None else ms[layer]
