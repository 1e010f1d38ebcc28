"""Test configuration: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): multi-node is
simulated on one machine; here multi-chip is simulated with virtual CPU
devices so sharding/collective paths are exercised without TPU hardware.
The env vars are for the subprocesses tests start (examples, workers);
this process selects its backend through jax.config, which must happen
before any backend use.
"""
import os
import tempfile

os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
  os.environ['XLA_FLAGS'] = (
      _flags + ' --xla_force_host_platform_device_count=8').strip()

import jax

jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 8)

# Example subprocesses call enable_compilation_cache(): place their cache
# outside the checkout (the chip tool copies the tree as it stands). Set
# AFTER the jax import so this process itself keeps caching off.
os.environ.setdefault('JAX_COMPILATION_CACHE_DIR',
                      os.path.join(tempfile.gettempdir(),
                                   'glt_test_xla_cache'))

# pytest-xdist workers inherit that variable and so DO cache there. Key
# it as the program does (utils.enable_compilation_cache): jax's default
# key strips instruction metadata, and a test that reads the glt.* scopes
# out of a compiled program would be served another compile's names.
jax.config.update('jax_compilation_cache_include_metadata_in_key', True)

import signal

import numpy as np
import pytest

# ---------------------------------------------------------- per-test alarm
# A deadlocked distributed test (hung channel recv, stuck barrier, dead
# subprocess join) must fail fast with a diagnosable error instead of
# eating the whole tier-1 suite budget. pytest-timeout is not in the
# image, so this is the conftest-level equivalent: a SIGALRM fires after
# GLT_TEST_TIMEOUT seconds (default 300) and raises in the test's main
# thread. Override per test with @pytest.mark.timeout(seconds).
# Posix-only and main-thread-only — exactly where pytest runs test code.

def _parse_timeout(raw, default=300):
  """Hardened GLT_TEST_TIMEOUT parse: a malformed value must warn and
  fall back, never crash collection of the whole suite (the same
  discipline as GLT_SPAN_BUFFER / GLT_HEARTBEAT_INTERVAL — regression-
  tested in tests/test_recovery.py)."""
  if raw in (None, ''):
    return default
  try:
    return int(raw)
  except (TypeError, ValueError):
    import warnings
    warnings.warn(f'GLT_TEST_TIMEOUT={raw!r} is not an integer — '
                  f'using the default {default}s')
    return default


_DEFAULT_TIMEOUT = _parse_timeout(os.environ.get('GLT_TEST_TIMEOUT'))


class TestDeadlineError(Exception):
  """Raised in-test when the per-test alarm fires."""


def pytest_configure(config):
  config.addinivalue_line(
      'markers', 'timeout(seconds): override the per-test alarm '
      f'(default GLT_TEST_TIMEOUT={_DEFAULT_TIMEOUT}s)')


def _alarm_wrapper(item, nursery):
  """Arm SIGALRM around one test phase; a hang in fixture setup or
  teardown must fail fast too, not just one in the test body."""
  marker = item.get_closest_marker('timeout')
  seconds = int(marker.args[0]) if marker and marker.args \
      else _DEFAULT_TIMEOUT
  if seconds <= 0 or not hasattr(signal, 'SIGALRM'):
    return (yield)

  def on_alarm(signum, frame):
    raise TestDeadlineError(
        f'test {nursery} exceeded the {seconds}s per-test alarm '
        '(GLT_TEST_TIMEOUT / @pytest.mark.timeout) — likely a deadlock '
        'in a distributed path; see the traceback for where it hung')

  prev = signal.signal(signal.SIGALRM, on_alarm)
  signal.alarm(seconds)
  try:
    return (yield)
  finally:
    signal.alarm(0)
    signal.signal(signal.SIGALRM, prev)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
  return (yield from _alarm_wrapper(item, 'setup'))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
  return (yield from _alarm_wrapper(item, 'call'))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
  return (yield from _alarm_wrapper(item, 'teardown'))


@pytest.fixture
def rng():
  return np.random.default_rng(0)


# ------------------------------------------------------- strict guard rails
# The scanned-epoch suites run with GLT_STRICT=1 by default: the epoch
# program regions in loader.ScanTrainer / loader.DistScanTrainer then
# execute under jax.transfer_guard('disallow') + jax.checking_leaks
# (utils/strict.py), so a change that sneaks an implicit device<->host
# transfer or a leaked tracer into a scan body fails these tests even
# when its numerics are still correct — the runtime complement of the
# graftlint static pass (docs/static_analysis.md). Export GLT_STRICT=0
# to debug a failure with the guards off.

_STRICT_MODULES = ('test_scan_epoch', 'test_dist_scan_epoch',
                   'test_serving', 'test_storage', 'test_recovery',
                   'test_remote_scan', 'test_dist_oversub',
                   # round 19: the typed (hetero) fast paths must hold
                   # their bit-identity + dispatch budgets with the
                   # guard rails armed, same as their homo counterparts
                   'test_capacity_plans',
                   # the run program must hold its zero-retrace /
                   # budget contracts with the guard rails armed
                   'test_run_epoch',
                   # r13 kernel parity suites: the fused-hop stream and
                   # gather-v2 tests must hold with the strict guard
                   # rails armed (the kernels ride inside guarded scan
                   # bodies in production)
                   'test_ops')


@pytest.fixture(autouse=True)
def _strict_scanned_epochs(request, monkeypatch):
  if request.node.module.__name__ in _STRICT_MODULES and \
      os.environ.get('GLT_STRICT', '') == '':
    monkeypatch.setenv('GLT_STRICT', '1')
  yield


# ------------------------------------------------------ wall-budget canary
# The tier-1 harness kills the suite at GLT_TIER1_BUDGET_S (870 s,
# ROADMAP.md) — and container-load variance is ±120 s/run, so a suite
# that *passes* near the ceiling is one noisy run away from a timeout
# nobody diagnosed (it happened in PR 3: restored tests silently
# outgrew the budget until the harness started killing runs). Warn
# LOUDLY when the run consumes more than GLT_TIER1_CANARY_FRAC (default
# 80%) of the budget, so the next PR sees the drift in green output and
# moves variants under the `slow` marker before the harness does it the
# hard way.

_SESSION_T0 = None
_TIER1_BUDGET_S = float(os.environ.get('GLT_TIER1_BUDGET_S', '870'))
_TIER1_CANARY_FRAC = float(os.environ.get('GLT_TIER1_CANARY_FRAC', '0.8'))


def pytest_sessionstart(session):
  global _SESSION_T0
  import time
  _SESSION_T0 = time.monotonic()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
  import time
  if _SESSION_T0 is None or _TIER1_BUDGET_S <= 0:
    return
  elapsed = time.monotonic() - _SESSION_T0
  threshold = _TIER1_CANARY_FRAC * _TIER1_BUDGET_S
  if elapsed <= threshold:
    return
  terminalreporter.write_line('')
  terminalreporter.write_line(
      f'WALL-BUDGET CANARY: this pytest run took {elapsed:.0f}s — over '
      f'{100 * _TIER1_CANARY_FRAC:.0f}% of the {_TIER1_BUDGET_S:.0f}s '
      'tier-1 timeout (ROADMAP.md). Container-load variance is '
      '~±120 s/run, so the suite is at risk of being KILLED by the '
      'harness: move the heaviest redundant variants under the `slow` '
      'marker (keep one tier-1 representative per family) before '
      'adding more tests.', yellow=True, bold=True)
