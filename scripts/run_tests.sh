#!/usr/bin/env bash
# Per-file test runner: one pytest process per test file.
#
# Why not one big `pytest tests/`: on this image the XLA:CPU compiler
# intermittently segfaults (and its AOT serializer aborts) late in a
# long-lived process after many hundred compilations — the same test
# passes in a fresh process.  Per-file processes bound the blast radius
# and mirror the reference CI, which runs each case as its own
# executable under ctest (cmake/testing/pmmg_tests.cmake).
set -u
cd "$(dirname "$0")/.."

# --lint: static invariant gate (scripts/lint_check.py) — R1-R10 AST
# rules over the whole tree in seconds, no jax import, no compiles:
# jit-hygiene, hot-path host-sync, obs print routing, PARMMG_* knob
# registry, jaxcompat shim discipline, static telemetry names, plus
# the flow-sensitive provers (R8 SPMD collective alignment, R9 lock
# discipline, R10 shape-ladder escapes).  Zero unsuppressed
# non-baselined violations allowed (lint_baseline.json is the
# grandfathered burn-down list; R4 runs with no baseline at all).
# Extra args pass through: `run_tests.sh --lint --sarif out.sarif`,
# `run_tests.sh --lint --changed-only`, `--rules R8,R9`, `-v`.
if [ "${1:-}" = "--lint" ]; then
    shift
    exec python scripts/lint_check.py "$@"
fi

# The compile-heavy gates below pay minutes of XLA:CPU compile — run
# the seconds-cheap static lint first so hygiene violations fail fast.
if [ "${1:-}" = "--ledger" ] || [ "${1:-}" = "--obs" ] \
        || [ "${1:-}" = "--chaos" ] || [ "${1:-}" = "--serve" ] \
        || [ "${1:-}" = "--multihost" ]; then
    python scripts/lint_check.py || exit 1
fi

# --ledger: compile-governor budget gate only — run the steady-state
# migration scenario (G=1 AND the grouped G=2 layout, so the grouped
# analysis/exchange entry points are budget-asserted too), the chunked
# grouped-pass scenario asserting the quiet-group scheduler introduces
# ZERO new compile families vs always-dispatch, and the serving_gate
# (a warm multi-tenant pool serving 2 tenants of different bucket
# sizes adds zero groups.* families vs the batch grouped path in the
# same process, bit-for-bit parity included); fail if any registered
# entry point exceeded its compiled-variant budget
# (scripts/ledger_check.py; its --diff mode compares two BENCH/SCALE
# artifacts for variant-count regressions).
if [ "${1:-}" = "--ledger" ]; then
    exec env JAX_PLATFORMS=cpu python scripts/ledger_check.py
fi

# --obs: observability gate (scripts/obs_check.py) — a tiny grouped
# pass with PARMMG_TRACE armed must replay to the same per-phase totals
# Timers.report prints (the spans ARE the timer measurements), and
# trace-on vs trace-off must add ZERO groups.* compile families
# (telemetry is host bookkeeping, never a new program).
if [ "${1:-}" = "--obs" ]; then
    exec env JAX_PLATFORMS=cpu python scripts/obs_check.py
fi

# --chaos: fault-injection gate (scripts/chaos_check.py) — every
# PARMMG_FAULT site provokes its REAL failure path in-process and must
# land on its documented escalation-ladder step: recovered bit-for-bit
# (transient dispatch fault, checkpoint/resume) or degraded with a
# conforming mesh (retry exhaustion -> LOWFAILURE, serve quarantine
# with cohort parity).  Hang drills (hang=S fault action): a wedged
# chunk dispatch / band exchange is converted by its
# PARMMG_DEADLINE_* watchdog into the same retry ladder, bit-for-bit.  Ends with a 3-run fixed-seed smoke of the seeded
# chaos-soak harness (scripts/chaos_soak.py; the full campaign is
# standalone).  The zero-fault run with the resilience wiring active
# must be bit-neutral and add ZERO new groups.* compile families.
if [ "${1:-}" = "--chaos" ]; then
    exec env JAX_PLATFORMS=cpu python scripts/chaos_check.py
fi

# --serve: serving-daemon gate (scripts/serve_check.py) — start the
# pool daemon on an ephemeral port, submit 2 small tenants over
# localhost HTTP, fetch, assert bit-for-bit parity with their
# standalone grouped runs and ZERO new groups.* compile families after
# the standalone warmup, then a clean shutdown (threads joined).
if [ "${1:-}" = "--serve" ]; then
    exec env JAX_PLATFORMS=cpu python scripts/serve_check.py
fi

# --multihost: pod-runtime gate (scripts/multihost_check.py) — a
# 2-process localhost run must be bit-identical to the 1-process dist
# path, every worker must pay ~zero compiles through the shared warm
# cache, the hot path must perform ZERO process_allgather bytes
# (mh.hot_allgather_bytes), and a worker killed mid-run must resume
# from its per-pass checkpoint bit-identically — as must a worker
# WEDGED mid-run (hang=600 fault action): its heartbeat lease
# (--lease) expires, the supervisor kills the pack and the resumed
# run lands on the same bits.  First invocation warms the repo-local
# .jax_cache_mh; repeats run warm.
if [ "${1:-}" = "--multihost" ]; then
    exec env JAX_PLATFORMS=cpu python scripts/multihost_check.py
fi

fail=0
# static lint first: costs seconds, fails before any compile is paid
echo "=== lint (static invariants R1-R10)"
python scripts/lint_check.py || fail=1
for f in tests/test_*.py; do
    echo "=== $f"
    timeout 2000 python -m pytest "$f" -q --no-header 2>&1 | tail -2
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
        echo "!!! $f exited $rc"
        fail=1
    fi
done
exit $fail
