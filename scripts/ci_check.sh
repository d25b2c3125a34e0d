#!/usr/bin/env bash
# CI gate: the static invariant analyzer (zero unsuppressed findings on
# src/repro/core), clean test collection (hard requirement — a module
# that fails to import takes its whole file's tests with it silently),
# the fast unit tier under a timeout, the bounded stress/property tier,
# the bounded crash-injection tier (SIGKILL a writer subprocess
# mid-write, recover, check invariants), the dynamic race tier
# (run the stack under repro.core.locktrace and cross-check observed
# lock orders against the static lock graph).  Performance is measured
# on the chip by benchmarks/lms_bench, not here.  See tests/README.md.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "[1/6] invariant analyzer (scripts/lms_lint.py src/repro/core)"
python scripts/lms_lint.py src/repro/core

echo "[2/6] collection gate (pytest --collect-only)"
python -m pytest --collect-only -q tests/ > /dev/null

echo "[3/6] fast unit tier (timeout ${CI_FAST_TIMEOUT:-600}s)"
timeout "${CI_FAST_TIMEOUT:-600}" python -m pytest -q \
    -m "not stress and not crash and not race" \
    tests/test_line_protocol.py \
    tests/test_tsdb.py \
    tests/test_rollup.py \
    tests/test_shard.py \
    tests/test_wal.py \
    tests/test_router.py \
    tests/test_ingest.py \
    tests/test_federation.py \
    tests/test_lms_stack.py \
    tests/test_query.py \
    tests/test_analysis.py \
    tests/test_analysis_engine.py \
    tests/test_coldstore.py \
    tests/test_analyzer.py \
    tests/test_quantile_sketch.py \
    tests/test_marker.py

echo "[4/6] stress/property tier (bounded; timeout ${CI_STRESS_TIMEOUT:-600}s)"
# Bounded example counts keep CI deterministic-ish and quick; raise the
# bounds locally to soak (LMS_STRESS_SCALE=10 LMS_PROPERTY_EXAMPLES=500).
LMS_STRESS_SCALE="${LMS_STRESS_SCALE:-1}" \
LMS_PROPERTY_EXAMPLES="${LMS_PROPERTY_EXAMPLES:-30}" \
timeout "${CI_STRESS_TIMEOUT:-600}" python -m pytest -q -m stress tests/

echo "[5/6] crash-injection tier (bounded; timeout ${CI_CRASH_TIMEOUT:-300}s)"
# Real SIGKILLs against a WAL writer subprocess; raise LMS_CRASH_ITERS
# locally to soak (LMS_CRASH_ITERS=20).
LMS_CRASH_ITERS="${LMS_CRASH_ITERS:-3}" \
timeout "${CI_CRASH_TIMEOUT:-300}" python -m pytest -q -m crash tests/

echo "[6/6] race tier (timeout ${CI_RACE_TIMEOUT:-300}s)"
timeout "${CI_RACE_TIMEOUT:-300}" python -m pytest -q -m race tests/

echo "ci_check: OK"
