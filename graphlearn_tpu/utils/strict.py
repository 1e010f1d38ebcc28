"""Strict runtime guard rails for the scanned-epoch hot paths.

``GLT_STRICT=1`` turns the hot-path contracts graftlint checks
statically (graphlearn_tpu/analysis/) into RUNTIME tripwires: the
scanned epoch programs (``loader.ScanTrainer`` /
``loader.DistScanTrainer``) execute under

  * ``jax.transfer_guard('disallow')`` — any IMPLICIT device<->host
    transfer inside the epoch region raises instead of silently
    reintroducing the per-step sync the scan exists to remove.
    Explicit ``jax.device_put`` / ``jax.device_get``
    still work — the epoch boundary uses them deliberately.
  * ``jax.checking_leaks()`` — a traced value escaping its trace
    (captured by a host closure, stored on ``self``) raises at the
    leak, not at some later use.

The guard is scoped to the epoch program region — seed-matrix build,
chunk dispatch loop, metrics concat — NOT the epoch-boundary
bookkeeping (overflow-policy fetch, stats publish), which fetches
per-epoch by design. tests/conftest.py enables strict mode for the
scanned-epoch test modules, so the equivalence suites double as
guard-rail regression tests; see docs/static_analysis.md.
"""
import contextlib
import os

ENV_VAR = 'GLT_STRICT'


def strict_enabled() -> bool:
  """True when GLT_STRICT is set to anything but '' / '0'."""
  return os.environ.get(ENV_VAR, '') not in ('', '0')


@contextlib.contextmanager
def strict_guards():
  """Transfer-guard('disallow') + checking_leaks when GLT_STRICT is on;
  a no-op otherwise (zero overhead in production: one env check at
  entry). Reads the env var per call so tests can toggle it with
  monkeypatch.setenv without re-importing anything."""
  if not strict_enabled():
    yield
    return
  import jax
  with jax.transfer_guard('disallow'), jax.checking_leaks():
    yield
