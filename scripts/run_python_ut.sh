#!/usr/bin/env bash
# CI entry: full unit-test suite on the virtual CPU mesh (the reference's
# scripts/run_python_ut.sh equivalent). Safe on machines without a TPU —
# tests/conftest.py forces the CPU backend with 8 virtual devices.
set -euo pipefail
cd "$(dirname "$0")/.."
# static gate first: graftlint + ruff + log schema (seconds, no jax) —
# a hot-path invariant violation fails the run before any test runs
bash scripts/lint.sh
python -m pytest tests/ -q "$@"
