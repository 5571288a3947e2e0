#!/usr/bin/env bash
# The two structural checks a PR must pass, in the order that fails
# fastest: the static-analysis gates, then the tier-1 tests as the driver
# runs them (the `commands` of /root/TESTS_LAST_RUN.json, without the log
# plumbing the driver adds). Neither states a speed: speed is measured on
# the chip by benchmark/run.py (BENCHMARK.json) and stated in PERF.md and
# PERF_LEDGER.jsonl. Usage: scripts/ci_check.sh [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== wukong-analyze (static gates) =="
python -m wukong_tpu.analysis  # exits non-zero on any gate violation

echo "== tier-1 pytest (-m 'not slow', six workers, one file per worker) =="
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly "$@"

echo "ci_check: all green"
