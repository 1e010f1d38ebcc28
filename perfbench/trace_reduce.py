"""From a profiler trace to numbers: the benchmark's own reduction.

The lane-level reading of a v5e trace (``XLA Modules`` holds the programs,
``XLA Ops`` the operations; ``graphlearn_tpu/utils/trace.py`` reads the same
lanes, and later PRs may change the program but not the yardstick): the
loader, the union of the device's busy intervals, the idle share, and the
idle gaps labelled by what the host was doing. Time per scope and per
operation is ``scope_reduce.py``'s, as self time; a sum of whole programs'
or operations' durations has no reader since PR 28 (the per-batch cell that
wants one brings it with its readers).

Input is the ``*.trace.json.gz`` the JAX profiler writes: ``M`` events name
processes (``/device:TPU:0``, ``/host:CPU``) and threads (lanes); ``X``
events carry ``ts`` and ``dur`` in microseconds on one clock for host and
device. ``perfbench/fixtures/trace_v5e_cut.json`` is a hand-cut real one.
"""
import collections
import glob
import gzip
import json
import re

PROGRAM_LANE = 'XLA Modules'
OP_LANE = 'XLA Ops'
_SUFFIX = re.compile(r'\.\d+$')
NO_SPAN = 'between calls'       # label of an idle gap no annotation covers


def load(path_or_dir):
  """``(device_events, host_events)`` of a trace file, or of the newest
  ``*.trace.json.gz`` under a directory. Every event gains ``lane`` (its
  thread's name) and device events ``chip`` (their process's name)."""
  path = path_or_dir
  if not path.endswith(('.json', '.json.gz')):
    found = sorted(glob.glob(path + '/**/*.trace.json.gz', recursive=True))
    if not found:
      return [], []
    path = found[-1]
  opener = gzip.open if path.endswith('.gz') else open
  with opener(path, 'rt') as f:
    events = json.load(f).get('traceEvents', [])
  procs, lanes = {}, {}
  for e in events:
    if e.get('ph') == 'M' and e.get('name') == 'process_name':
      procs[e['pid']] = e['args'].get('name', '')
    elif e.get('ph') == 'M' and e.get('name') == 'thread_name':
      lanes[(e['pid'], e.get('tid'))] = e['args'].get('name', '')
  device, host = [], []
  for e in events:
    if e.get('ph') != 'X' or 'dur' not in e:
      continue
    proc = procs.get(e.get('pid'), '')
    e = dict(e, lane=lanes.get((e.get('pid'), e.get('tid')), ''))
    if 'TPU' in proc:
      device.append(dict(e, chip=proc))
    elif proc.startswith('/host'):
      host.append(e)
  return device, host


def _merged(intervals):
  out = []
  for lo, hi in sorted(intervals):
    if out and lo <= out[-1][1]:
      out[-1][1] = max(out[-1][1], hi)
    else:
      out.append([lo, hi])
  return out


def window_of(host, prefix='perfbench.'):
  """(start, end) in microseconds of the harness's own annotations —
  the traced window; None when the trace holds none."""
  mine = [e for e in host if e.get('name', '').startswith(prefix)]
  if not mine:
    return None
  return (min(e['ts'] for e in mine), max(e['ts'] + e['dur'] for e in mine))


def busy(device, window=None):
  """``(busy_s, window_s, gaps)``: seconds in which an operation ran on
  the device (the union of the ``XLA Ops`` intervals, nested ones counted
  once; averaged over the chips in the trace), the window's length, and
  the first chip's idle gaps as ``(start_us, end_us)``. Without a window
  the span from the first to the last device operation is taken."""
  chips = collections.defaultdict(list)
  for e in device:
    if e['lane'] == OP_LANE:
      chips[e['chip']].append((e['ts'], e['ts'] + e['dur']))
  if not chips:
    return 0.0, 0.0, []
  if window is None:
    window = (min(lo for iv in chips.values() for lo, _ in iv),
              max(hi for iv in chips.values() for _, hi in iv))
  w0, w1 = window
  total, gaps = 0.0, []
  for i, chip in enumerate(sorted(chips)):
    merged = [[max(lo, w0), min(hi, w1)] for lo, hi in _merged(chips[chip])
              if hi > w0 and lo < w1]
    total += sum(hi - lo for lo, hi in merged)
    if i == 0:
      edges = [w0] + [t for iv in merged for t in iv] + [w1]
      gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
              if edges[j + 1] > edges[j]]
  return total / len(chips) / 1e6, (w1 - w0) / 1e6, gaps


def label_gaps(gaps, host, top=10, prefix='perfbench.'):
  """The ``top`` longest idle gaps as ``[label, seconds]``: the label is
  the innermost annotation named ``prefix...`` open at the gap's middle
  (``NO_SPAN`` when none is)."""
  mine = [e for e in host if e.get('name', '').startswith(prefix)]
  out = []
  for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
    mid = (lo + hi) / 2
    open_ = [e for e in mine if e['ts'] <= mid <= e['ts'] + e['dur']]
    label = (min(open_, key=lambda e: e['dur'])['name'][len(prefix):]
             if open_ else NO_SPAN)
    out.append([label, (hi - lo) / 1e6])
  return out
