#!/usr/bin/env bash
# Tier-1 verification: the exact command the roadmap pins, runnable from
# anywhere, plus the docs check, a test-count floor (suites only grow —
# a collection regression below the PR 5 count fails before pytest runs),
# and a benchmark smoke step. Extra args are forwarded to pytest (e.g.
# scripts/check.sh -k agg).
#
# CI-friendly (.github/workflows/ci.yml runs this verbatim): every phase
# emits a "[check] phase <name> took Ns" timing line so slow phases show
# up in the job log, and a failed collection propagates pytest's own exit
# code (with its log tail) instead of burying it in the floor arithmetic.
set -euo pipefail
cd "$(dirname "$0")/.."

phase_start=$SECONDS
phase() { # phase <name>: report the wall time of the phase that just ended
  echo "[check] phase ${1} took $(( SECONDS - phase_start ))s"
  phase_start=$SECONDS
}

python scripts/check_docs.py
phase docs

TEST_FLOOR=494  # collected count after the TPU bring-up; raise, never lower
collect_log=$(mktemp)
collect_status=0
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest --collect-only -q \
  >"$collect_log" 2>&1 || collect_status=$?
if [ "$collect_status" -ne 0 ]; then
  echo "FAIL: pytest collection failed (exit $collect_status)" >&2
  tail -n 40 "$collect_log" >&2
  rm -f "$collect_log"
  exit "$collect_status"
fi
# prefer pytest's own "N tests collected" summary; fall back to counting
# column-0 node ids (warning lines mentioning '::' are indented and must
# not inflate the floor count)
collected=$(grep -Eo '^[0-9]+ tests? collected' "$collect_log" | tail -1 | cut -d' ' -f1 || true)
if [ -z "$collected" ]; then
  collected=$(grep -c '^[^ ]*::' "$collect_log" || true)
fi
rm -f "$collect_log"
if [ "$collected" -lt "$TEST_FLOOR" ]; then
  echo "FAIL: collected $collected tests < floor $TEST_FLOOR (lost tests?)" >&2
  exit 1
fi
echo "test-count floor OK ($collected >= $TEST_FLOOR)"
phase collect

# The wire suites spawn real worker subprocesses; a wedged socket must
# fail the phase with its log tail, never stall CI. Override the budget
# with PYTEST_TIMEOUT_S (seconds) for slow machines.
PYTEST_TIMEOUT_S=${PYTEST_TIMEOUT_S:-3600}
pytest_log=$(mktemp)
pytest_status=0
timeout --signal=TERM --kill-after=30 "$PYTEST_TIMEOUT_S" \
  env PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@" \
  >"$pytest_log" 2>&1 || pytest_status=$?
if [ "$pytest_status" -eq 124 ] || [ "$pytest_status" -eq 137 ]; then
  echo "FAIL: pytest exceeded ${PYTEST_TIMEOUT_S}s (hung socket test?); last 60 log lines:" >&2
  tail -n 60 "$pytest_log" >&2
  rm -f "$pytest_log"
  exit 124
fi
if [ "$pytest_status" -ne 0 ]; then
  tail -n 100 "$pytest_log" >&2
  rm -f "$pytest_log"
  exit "$pytest_status"
fi
tail -n 15 "$pytest_log"
rm -f "$pytest_log"
phase pytest

# the smoke rows land in a file so CI can upload THIS run's numbers as an
# artifact next to the committed BENCH trajectory
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.run --smoke > BENCH_smoke_rows.csv
echo "benchmark smoke OK ($(wc -l < BENCH_smoke_rows.csv) rows in BENCH_smoke_rows.csv)"
phase bench_smoke
