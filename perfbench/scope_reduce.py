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
union of its busy intervals (a sum of durations counts a wrapper and its
children: there a ``while`` repeats its whole body).

``layers(run)`` is what the six ``scan_*`` / ``host_gap_ms`` readers share.
It reduces the traced slice — the window's own chunk program — once per
run, keeps the result in ``run``, and prints one ``perfbench:`` line with
the sub-scope split, ``host_gap_ms`` by innermost span and what tracing
cost. With a program that has no ``glt.`` scope or span (the parent of
PR 26) every reader finds nothing and returns ``None``.

``device_ops`` and ``idle_gaps`` are the two lists of a traced run's
``breakdown``: self time by scope and op class, and the longest idle gaps by
what the host was doing.
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


def timed_of(slice_):
  """``self_times`` of a traced slice, reduced once and kept in it: the
  ``breakdown``, ``layers`` and ``scan_draw_ms.draws`` all read it."""
  if 'self_times' not in slice_:
    slice_['self_times'] = self_times(slice_['device'])
  return slice_['self_times']


def by_scope_and_op(timed):
  """``{(sub-scope or unscoped, op class): seconds}`` of ``self_times``'
  value, averaged over its chips — the one accumulation ``by_scope`` and
  ``device_ops`` fold."""
  timed, chips = timed
  acc = collections.defaultdict(float)
  for e, self_us in timed:
    path = scope_path(e)
    op = trace_reduce._SUFFIX.sub('', e.get('name', ''))
    acc[sub_scope(path) if path else UNSCOPED, op] += self_us / 1e6 / chips
  return acc


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
  loose = {}
  for (scope, op), s in by_scope_and_op((timed, chips)).items():
    scopes[scope] += s
    if scope == UNSCOPED:
      loose[op] = s
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


def device_ops(device, top=10, timed=None):
  """``[[<sub-scope or unscoped>:<op class>, seconds]]``, the ``top``
  largest by self time (averaged over the chips): they add up to no more
  than the slice's busy time, and a ``while`` counts only its own."""
  acc = by_scope_and_op(timed or self_times(device))
  return [[f'{scope}:{op}', s] for (scope, op), s in
          sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def idle_gaps(gaps, host, top=10):
  """The ``top`` longest idle gaps as ``[label, seconds]``: the innermost
  of the program's ``glt.`` spans open at the gap's middle, by its full
  name; where none is, the harness's own annotation (``run_epoch``,
  ``host_fetch``, ``between calls``)."""
  longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
  inner = trace_reduce.label_gaps(longest, host, top, PREFIX)
  outer = trace_reduce.label_gaps(longest, host, top)
  return [[outer_label, s] if label == trace_reduce.NO_SPAN
          else [PREFIX + label, s]
          for (label, s), (outer_label, _) in zip(inner, outer)]


def breakdown(slice_):
  """The last line's ``breakdown`` of a traced slice (``device`` and
  ``host`` events, the device's idle ``gaps``)."""
  return {'device_ops': device_ops(slice_['device'], timed=timed_of(slice_)),
          'idle_gaps': idle_gaps(slice_['gaps'], slice_['host'])}


def layers(run):
  """The traced slice by layer, once per run: ``{'ms': {glt.sample,
  glt.collate, glt.train, unscoped} ms/step or None, 'host_gap_ms':
  ms/step or None}``.
  The first call prints the ``perfbench:`` line described above."""
  if 'scope_reduce' in run:
    return run['scope_reduce']
  a = run['scan']
  out = {'ms': None, 'host_gap_ms': None}
  line = {}
  if a['steps']:
    timed_a = timed_of(a)
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
  if line:
    print('perfbench: ' + json.dumps({'scope_reduce': line}), flush=True)
  run['scope_reduce'] = out
  return out


def layer_ms(run, layer):
  ms = layers(run)['ms']
  return None if ms is None else ms[layer]
