"""Serving-daemon gate (scripts/run_tests.sh --serve).

End-to-end over localhost HTTP, in one process (so the compile ledger
is shared and the zero-new-family assertion has teeth):

1. stage 2 small tenants (the warm-pool fixture shapes: cube 2 + cube
   3, the same ladder buckets every other gate compiles) and run each
   standalone ``grouped_adapt_pass(ngroups=1)`` — the parity reference
   AND the warmup that compiles every ``groups.*`` family serving may
   touch;
2. start a PoolDaemon on an ephemeral port, submit both tenants as raw
   arrays through ServeClient (base64 npz), wait, fetch;
3. assert: both served; each fetched result BIT-IDENTICAL to its
   standalone run (mesh fields + metric — the staging rule is shared,
   so parity is by construction testable); daemon serving added ZERO
   ``groups.*`` compile families after the standalone warmup; /healthz
   live; /metrics parses as Prometheus exposition; clean shutdown
   (threads joined).

Exit 0 green / 1 red.  CPU backend, persistent cache off
(ledger_check.py sequence).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("PARMMG_FAULT", None)

import jax  # noqa: E402

from parmmg_tpu.utils.compilecache import disable_persistent_cache  # noqa: E402
disable_persistent_cache()

import numpy as np  # noqa: E402

FAILS: list[str] = []


def check(ok: bool, msg: str) -> None:
    tag = "ok" if ok else "SERVE GATE FAIL"
    print(f"  {tag}: {msg}", file=sys.stdout if ok else sys.stderr)
    if not ok:
        FAILS.append(msg)


def main() -> int:
    from parmmg_tpu.core.mesh import MESH_FIELDS
    from parmmg_tpu.obs.metrics import parse_prometheus
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.serve.admission import stage_arrays
    from parmmg_tpu.serve.client import ServeClient
    from parmmg_tpu.serve.daemon import PoolDaemon
    from parmmg_tpu.utils.compilecache import (reset_ledger,
                                               variants_by_prefix)
    from parmmg_tpu.utils.fixtures import cube_mesh

    cycles = 2
    classes = ((2, 0.55), (3, 0.5))

    # ---- 1. standalone warmup + parity references -----------------------
    print("--- serve gate: standalone warmup (parity references)")
    reset_ledger()
    raw = {}
    refs = {}
    for n, h in classes:
        vert, tet = cube_mesh(n)
        met = np.full(4 * len(vert), h)     # full-capP metric, h pads
        raw[n] = (vert, tet, met)
        mesh, met_s = stage_arrays(vert, tet, met=met)
        out, met_m, _ = grouped_adapt_pass(mesh, met_s, 1, cycles=cycles)
        jax.block_until_ready(out.vert)
        refs[n] = (out, np.asarray(met_m))
    v0 = variants_by_prefix("groups.")
    check(v0.get("groups.adapt_block", 0) >= 1,
          "warmup exercises groups.adapt_block")

    # ---- 2. daemon serving over localhost HTTP --------------------------
    print("--- serve gate: daemon round-trip (2 tenants over HTTP)")
    daemon = PoolDaemon(port=0, slots_per_bucket=2, chunk=1,
                        cycles=cycles)
    daemon.start()
    try:
        cl = ServeClient(port=daemon.port)
        check(cl.health().get("ok") is True, "daemon /healthz live")
        tids = {}
        for n, h in classes:
            vert, tet, met = raw[n]
            tids[n] = cl.submit(vert=vert, tet=tet, met=met,
                                tenant=f"n{n}")
        for n in tids:
            got = cl.wait(tids[n], timeout_s=600)
            check(got["state"] == "done",
                  f"tenant n{n} served ({got['state']}: "
                  f"{got.get('reason', '')})")

        # ---- 3. bit-for-bit parity vs the standalone runs ---------------
        for n, _h in classes:
            arrays = cl.fetch(tids[n])
            ref, kref = refs[n]
            ok = all(
                (arrays[f] == np.asarray(getattr(ref, f))).all()
                for f in MESH_FIELDS) and (arrays["met"] == kref).all()
            check(ok, f"tenant n{n} fetched result bit-identical to "
                      "its standalone grouped run")

        v1 = variants_by_prefix("groups.")
        check(v1 == v0, f"daemon serving added zero groups.* compile "
                        f"families ({v0} -> {v1})")
        series = parse_prometheus(cl.metrics_text())
        check(any(name == "parmmg_serve_dispatches_total"
                  for name, _ in series),
              "/metrics exposes serve counters in Prometheus text")
        rep = cl.report()
        check(rep["served"] == len(classes) and rep["failed"] == 0,
              f"daemon report: {rep['served']} served, "
              f"{rep['failed']} failed")
    finally:
        daemon.shutdown()
    check(not daemon.alive(), "daemon threads joined on shutdown")

    if FAILS:
        print(f"\nserve gate FAILED ({len(FAILS)} checks):",
              file=sys.stderr)
        for f in FAILS:
            print(f"  {f}", file=sys.stderr)
        return 1
    print("\nserve OK: daemon served both tenants bit-identical to "
          "standalone with zero new compile families, clean shutdown")
    return 0


if __name__ == "__main__":
    sys.exit(main())
