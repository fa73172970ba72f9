"""Observability gate (scripts/run_tests.sh --obs).

Runs a tiny chunked grouped pass twice in one process — trace sink OFF,
then ON — and FAILS (exit 1) unless:

1. **replay parity**: the JSONL trace replays to the same per-phase
   totals the run's ``Timers`` registry reports (±1%) — the spans ARE
   the timer measurements (utils/timers.py emits them), so any drift
   means the spine forked the numbers;
2. **zero compile cost**: the trace-on run adds ZERO ``groups.*``
   compile-ledger families versus the trace-off run (same process, jit
   caches warm) — tracing is host bookkeeping, never a new program;
3. the metrics spine registered the pass (``groups.dispatches`` > 0)
   and the Prometheus exposition round-trips through the parser.

CPU backend, persistent cache off (ledger_check.py sequence).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("PARMMG_TRACE", None)       # the sink is armed explicitly

import jax  # noqa: E402

from parmmg_tpu.utils.compilecache import disable_persistent_cache  # noqa: E402
disable_persistent_cache()

import numpy as np  # noqa: E402


def run_pass(tim):
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.adapt import AdaptStats
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.utils.fixtures import cube_mesh

    vert, tet = cube_mesh(2)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, 0.35, m.vert.dtype)
    st = AdaptStats()
    with tim("adaptation"):
        out, _, _ = grouped_adapt_pass(m, met, 3, cycles=2, stats=st,
                                       timers=tim)
    assert int(np.asarray(out.tmask).sum()) > 0
    return st


def main() -> int:
    from parmmg_tpu.obs import trace as otrace
    from parmmg_tpu.obs.metrics import REGISTRY, parse_prometheus
    from parmmg_tpu.utils.compilecache import (reset_ledger,
                                               variants_by_prefix)
    from parmmg_tpu.utils.timers import Timers

    # chunked dispatch so the pipeline segments (upload/compute/
    # download/writeback) exercise Timers.add absorption too
    prev = os.environ.get("PARMMG_GROUP_CHUNK")
    os.environ["PARMMG_GROUP_CHUNK"] = "1"
    rc = 0
    try:
        reset_ledger()
        # ---- run 1: trace sink OFF (ring only) -------------------------
        otrace.TRACER.configure(path=None)
        run_pass(Timers())
        v0 = variants_by_prefix("groups.")
        assert v0.get("groups.adapt_block", 0) >= 1, \
            "obs scenario no longer exercises groups.adapt_block"

        # ---- run 2: trace sink ON --------------------------------------
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "trace.jsonl")
            otrace.TRACER.configure(path=path)
            tim = Timers()
            st = run_pass(tim)
            otrace.TRACER.configure(path=None)
            v1 = variants_by_prefix("groups.")

            print("--- obs gate (trace-on grouped pass)")
            if v1 != v0:
                print("OBS COMPILE-FAMILY REGRESSIONS (trace on added "
                      f"variants): {v0} -> {v1}", file=sys.stderr)
                rc = 1

            # every line must parse; replay filtered to THIS Timers
            nlines = sum(1 for line in open(path) if line.strip()
                         and json.loads(line))
            tot, cnt = otrace.replay_totals(path, tim=tim.trace_id)
            if not tot:
                print("OBS: trace replay found no spans for the run's "
                      "Timers", file=sys.stderr)
                rc = 1
            for k, v in tim.acc.items():
                r = tot.get(k)
                if r is None or abs(r - v) > 0.01 * max(v, 1e-9):
                    print(f"OBS REPLAY MISMATCH: phase {k!r} timers="
                          f"{v:.6f}s trace={r}", file=sys.stderr)
                    rc = 1
                if cnt.get(k) != tim.count[k]:
                    print(f"OBS REPLAY MISMATCH: phase {k!r} count "
                          f"{tim.count[k]} != {cnt.get(k)}",
                          file=sys.stderr)
                    rc = 1
            extra = set(tot) - set(tim.acc)
            if extra:
                print(f"OBS REPLAY MISMATCH: trace has phases the "
                      f"Timers never recorded: {sorted(extra)}",
                      file=sys.stderr)
                rc = 1
            if rc == 0:
                print(f"obs replay OK: {len(tot)} phases match the "
                      f"Timers report exactly ({nlines} trace lines)")

        # ---- metrics spine ---------------------------------------------
        snap = REGISTRY.snapshot()
        if not snap["counters"].get("groups.dispatches"):
            print("OBS: groups.dispatches counter missing/zero after a "
                  "grouped pass", file=sys.stderr)
            rc = 1
        if st.group_dispatches <= 0:
            print("OBS: AdaptStats recorded no group dispatches",
                  file=sys.stderr)
            rc = 1
        parsed = parse_prometheus(REGISTRY.to_prometheus())
        if not any(name == "parmmg_groups_dispatches_total"
                   for name, _ in parsed):
            print("OBS: Prometheus exposition lost groups.dispatches",
                  file=sys.stderr)
            rc = 1
        if rc == 0:
            print(f"obs metrics OK: {len(snap['counters'])} counters, "
                  f"exposition round-trips ({len(parsed)} series)")
            print("\nobs gate OK: trace replay parity + zero new "
                  f"compile families ({v1})")
    finally:
        if prev is None:
            os.environ.pop("PARMMG_GROUP_CHUNK", None)
        else:
            os.environ["PARMMG_GROUP_CHUNK"] = prev
    return rc


if __name__ == "__main__":
    sys.exit(main())
