"""Profiler helpers, the dispatch counter and the counter shims.

``profile_trace`` captures a jax.profiler trace of a region (viewable in
TensorBoard / Perfetto; ``chip_smoke.py`` uses it);
``device_program_ms`` / ``device_op_ms`` reduce one. What the PROGRAM
puts on that timeline is not set here: the layers name their device work
``glt.sample`` / ``glt.collate`` / ``glt.train`` (``jax.named_scope``),
and every attached span of ``metrics/spans.py`` is a ``glt.<span>`` host
event — with no flag, and at no cost beyond a TraceMe no-op when no
session is open (docs/observability.md 'The glt. convention').

Usage:
    with glt.utils.profile_trace('/tmp/glt_trace'):
      state, losses, accs = trainer.run_epoch(state)
"""
import contextlib
import functools
import os
from typing import Callable, Iterator, Optional


@contextlib.contextmanager
def profile_trace(logdir: str) -> Iterator[None]:
  """Capture a jax.profiler trace for the enclosed region."""
  import jax
  jax.profiler.start_trace(logdir)
  try:
    yield
  finally:
    jax.profiler.stop_trace()


_trace_cache = {}   # (path, mtime) -> parsed events (newest entry only)


def _tpu_trace_events(trace_dir: str):
  """Duration ('X') events on TPU lanes from the NEWEST trace under
  ``trace_dir`` — the shared loader behind device_program_ms /
  device_op_ms (one place owns trace discovery + pid/lane mapping). Each
  event gains a ``'lane'`` key: its thread name ('XLA Modules', 'XLA
  Ops', 'Steps', ... on a TPU v5e; '' when the trace names no threads).
  The parsed result is memoized on (path, mtime) so program- and
  op-level views of the same trace parse it once."""
  import glob
  import gzip
  import json
  paths = sorted(glob.glob(trace_dir + '/**/*.trace.json.gz',
                           recursive=True))
  if not paths:
    return []
  key = (paths[-1], os.path.getmtime(paths[-1]))
  if key in _trace_cache:
    return _trace_cache[key]
  with gzip.open(paths[-1]) as f:
    t = json.load(f)
  pids, lanes = {}, {}
  for e in t.get('traceEvents', []):
    if e.get('ph') != 'M':
      continue
    if e.get('name') == 'process_name':
      pids[e['pid']] = e['args'].get('name', '')
    elif e.get('name') == 'thread_name':
      lanes[(e['pid'], e.get('tid'))] = e['args'].get('name', '')
  events = [dict(e, lane=lanes.get((e.get('pid'), e.get('tid')), ''))
            for e in t.get('traceEvents', [])
            if e.get('ph') == 'X' and 'dur' in e and
            'TPU' in pids.get(e.get('pid'), '')]
  _trace_cache.clear()            # keep only the newest trace in memory
  _trace_cache[key] = events
  return events


def device_program_ms(trace_dir: str):
  """Per-program average device ms from the newest trace under
  ``trace_dir``, keyed by jitted program name, TPU lane only — the
  device-trace clock the benchmarks use (PERF.md 'The clock').

  Returns {name: (avg_ms, call_count)}.
  """
  import collections
  durs = collections.defaultdict(lambda: [0.0, 0])
  for e in _tpu_trace_events(trace_dir):
    n = e.get('name', '')
    if n.startswith('jit_') and e['lane'] in ('', 'XLA Modules'):
      d = durs[n]
      d[0] += e['dur']
      d[1] += 1
  return {n: (tot / cnt / 1000.0, cnt) for n, (tot, cnt) in durs.items()}


def device_op_ms(trace_dir: str, top: int = 0, steps: int = 1,
                 strip_ids: bool = True):
  """Per-OP device ms from the newest trace under ``trace_dir`` (the TPU
  'XLA Ops' lane, non-program events) — the op-level companion of
  device_program_ms for kernel-attribution work (PERF.md byte audits).

  ``steps`` divides totals so units match device_program_ms's per-call
  averages (pass the traced step count). ``strip_ids`` groups op
  instances by XLA name with the trailing ``.NNN`` suffix removed
  (``fusion.123`` -> ``fusion``; bare-digit names like ``layer1`` are
  left intact) for op-class totals; pass False to keep instance names
  (for HLO correlation). Returns {name: (ms, count)}, sorted desc and
  truncated when ``top`` > 0.
  """
  import collections
  import re
  durs = collections.defaultdict(lambda: [0.0, 0])
  suffix = re.compile(r'\.\d+$')
  for e in _tpu_trace_events(trace_dir):
    n = e.get('name', '')
    # one lane only: the 'Steps' and 'Async XLA Ops' lanes cover the
    # same device time again under other names
    if n.startswith('jit_') or e['lane'] not in ('', 'XLA Ops'):
      continue
    if strip_ids:
      n = suffix.sub('', n)
    d = durs[n]
    d[0] += e['dur']
    d[1] += 1
  out = {n: (tot / 1000.0 / steps, cnt)
         for n, (tot, cnt) in durs.items()}
  if top:
    out = dict(sorted(out.items(), key=lambda kv: -kv[1][0])[:top])
  return out


# ---------------------------------------------------------------- dispatch
# Dispatch counting: every program launch costs host time the device may
# idle through, so the loaders/trainers instrument their dispatch sites
# and the tests and perfbench/ assert & report dispatches/epoch. The
# counter is a host-side convention — every hot-path program launch in
# this package calls record_dispatch() right before dispatching — which
# makes it exact for the instrumented paths and free (one None check)
# otherwise.


class DispatchCounter:
  """Per-site XLA program launch counts (see count_dispatches)."""

  def __init__(self):
    self.counts = {}

  @property
  def total(self) -> int:
    return sum(self.counts.values())

  def record(self, name: str = 'program'):
    self.counts[name] = self.counts.get(name, 0) + 1

  def subtotal(self, prefix: str) -> int:
    """Dispatches whose site name starts with ``prefix`` — the
    dispatch-budget tests assert per-subsystem slices ('dist_' for the
    distributed hot path) without being brittle to unrelated sites."""
    return sum(v for k, v in self.counts.items() if k.startswith(prefix))

  def __repr__(self):
    return f'DispatchCounter(total={self.total}, counts={self.counts})'


_dispatch_counter: Optional[DispatchCounter] = None


@contextlib.contextmanager
def count_dispatches(propagate: bool = False) -> Iterator[DispatchCounter]:
  """Count instrumented program dispatches in the enclosed region.

  Yields the active DispatchCounter; read ``.total`` / ``.counts`` after
  the block. Nesting restores the outer counter on exit; by default the
  inner region's dispatches are NOT added to the outer count (each
  counter owns its own region), which makes a nested bench region a
  silent blind spot in the outer budget — pass ``propagate=True`` to
  fold the inner region's per-site counts into the enclosing counter on
  exit (a no-op at top level)."""
  global _dispatch_counter
  prev, _dispatch_counter = _dispatch_counter, DispatchCounter()
  try:
    yield _dispatch_counter
  finally:
    inner, _dispatch_counter = _dispatch_counter, prev
    if propagate and prev is not None:
      for name, n in inner.counts.items():
        prev.counts[name] = prev.counts.get(name, 0) + n


def dispatch_snapshot() -> Optional[dict]:
  """Copy of the active count_dispatches region's per-site counts, or
  None when no region is active — the flight recorder's read hook
  (metrics/flight.py diffs two snapshots into per-epoch deltas without
  ever owning the region)."""
  return dict(_dispatch_counter.counts) \
      if _dispatch_counter is not None else None


def record_dispatch(name: str = 'program'):
  """Count one program dispatch under ``name`` (no-op when no
  count_dispatches() region is active). Call at the dispatch SITE, just
  before launching a jitted program — never inside traced code, where it
  would fire once per trace instead of once per call."""
  if _dispatch_counter is not None:
    _dispatch_counter.record(name)


def wrap_dispatch(fn: Callable, name: Optional[str] = None) -> Callable:
  """Counting wrapper for a jitted callable: each call records one
  dispatch under ``name`` (default: the function's name). For code
  outside this package (bench loops, tests) whose dispatch sites the
  built-in instrumentation doesn't cover."""
  label = name or getattr(fn, '__name__', 'program')

  @functools.wraps(fn)
  def wrapper(*args, **kwargs):
    record_dispatch(label)
    return fn(*args, **kwargs)

  return wrapper


# ---------------------------------------------------------------- counters
# Named event counters: the resilience layer (distributed/resilience.py)
# reports degradation events here — retries, failovers, worker restarts,
# injected faults — so a degraded-but-completed epoch is visible without
# log scraping. The distributed feature store publishes its ON-DEVICE
# hit/miss/overflow accumulator here too ('dist_feature.*'), via
# DistFeature.publish_stats() at EPOCH granularity — the counters ride
# the lookup program between publishes, so the hot loop never pays a
# device->host fetch for observability (PERF.md rules).
#
# These four are COMPATIBILITY SHIMS over the typed metric registry
# (graphlearn_tpu/metrics/registry.py, which subsumed the dict that
# used to live here): every call site keeps working, and the counters
# now appear in metrics.snapshot() / scrape_all() / the epoch flight
# recorder alongside gauges and histograms. Thread-safety moved with
# the store (the registry locks every mutation). Lazy import: metrics
# is a sibling package and utils must stay importable first.

_metric_registry = None


def _registry():
  global _metric_registry
  if _metric_registry is None:
    from ..metrics.registry import default_registry
    _metric_registry = default_registry()
  return _metric_registry


def counter_inc(name: str, n: int = 1):
  """Add ``n`` to the named event counter (creating it at 0)."""
  _registry().inc(name, n)


def counter_get(name: str) -> int:
  return _registry().counter_value(name)


def counters(prefix: str = '') -> dict:
  """Snapshot of counters, optionally filtered by name prefix."""
  return _registry().counters(prefix)


def reset_counters(prefix: str = ''):
  """Drop counters matching ``prefix`` (all by default). Shim note:
  this clears COUNTERS only, exactly the old dict semantics — gauges
  and histograms are reset through metrics.reset()."""
  _registry().reset_counters(prefix)
