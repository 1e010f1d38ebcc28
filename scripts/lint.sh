#!/usr/bin/env bash
# Static-analysis gate: graftlint (the repo-specific hot-path invariant
# checker, docs/static_analysis.md) + ruff (generic pyflakes/import
# hygiene, [tool.ruff] in pyproject.toml). Run from anywhere; exits
# non-zero on any finding. ruff is optional tooling — images without it
# skip that half with a notice (the graftlint half, pure stdlib ast,
# always runs; tests/test_analysis.py enforces the same zero-findings
# invariant inside the tier-1 suite, ruff or not).
set -uo pipefail
cd "$(dirname "$0")/.."

rc=0

echo "== graftlint =="
# the package walk includes every subpackage — the serving tier
# (graphlearn_tpu/serving/) and the out-of-core storage tier
# (graphlearn_tpu/storage/: tiered scan-chunk + plan programs, staging
# pipeline) are additionally scoped into the host-sync and
# dispatch-instrumentation rules via analysis/core.py Config, so their
# traced programs carry the same hot-path contracts as the scanned
# trainers
python -m graphlearn_tpu.analysis.lint graphlearn_tpu/ || rc=1

echo "== graftlint (bench profile) =="
# relaxed profile over the benchmark (perfbench/) and the chip
# bring-up check: the registry rules, bracket discipline and donation
# safety stay enforced — a benchmark that leaks spans or reads donated
# buffers measures garbage — while the hot-path scoping rules
# (host-sync/dispatch/prng/retrace/lock) are exempt: a benchmark
# host-syncs on purpose. The registry modules ride along so the name
# checks see the REGISTERED_* frozensets.
python -m graphlearn_tpu.analysis.lint --profile bench --no-baseline \
  perfbench/ chip_smoke.py \
  graphlearn_tpu/metrics/registry_names.py \
  graphlearn_tpu/utils/faults.py || rc=1

echo "== ruff =="
if python -m ruff --version >/dev/null 2>&1; then
  python -m ruff check graphlearn_tpu/ tests/ || rc=1
elif command -v ruff >/dev/null 2>&1; then
  ruff check graphlearn_tpu/ tests/ || rc=1
else
  echo "ruff not installed — skipping (config lives in pyproject.toml)"
fi

echo "== flight/span JSONL schema =="
# with no args this SELF-CHECKS: one record through each real recorder
# (flight + span), validated against metrics/logcheck.py — a
# recorder/schema drift fails lint in the change that introduces it.
# Pass file paths to validate captured GLT_RUN_LOG / GLT_SPAN_LOG
# trails from a run.
python -m graphlearn_tpu.metrics.logcheck || rc=1

exit "$rc"
