"""Serving bench: N mixed-size tenants through one warm pool.

The first artifact family whose throughput metric is **meshes/sec**,
not Mtets/sec (ROADMAP open item 3): a pool of bucketed group slots
serves independent tenant meshes through the SAME compiled group
programs the batch grouped path uses, so after a per-bucket warmup
every request runs compile-free.

Phases:

1. **warmup** — per tenant size class, one standalone
   ``grouped_adapt_pass(ngroups=1)`` run (the batch path: exactly what
   a non-serving user pays) + the quality pull.  This compiles every
   ``groups.*`` family serving will touch AND doubles as the parity
   reference;
2. **serve** — submit all tenants to one ServeDriver, run to
   completion, measure meshes/sec + per-tenant latency percentiles +
   slot occupancy;
3. **gates** — ``extra.ledger_regressions`` lists any ``groups.*``
   entry whose compiled-variant count grew between (1) and (2) (MUST
   be empty: serving adds zero compile families after warmup), and
   ``extra.parity_ok`` asserts one representative tenant per class is
   bit-for-bit identical (mesh fields + metric) to its standalone run.

``--stream`` (the SERVE_r02+ mode): instead of submitting everything
up front to an in-process driver, tenants arrive as a sustained
OPEN-LOOP stream (PARMMG_SERVE_STREAM_RATE tenants/sec) through a pool
DAEMON over localhost HTTP (ephemeral port, in-process so the compile
ledger stays shared): streaming mid-step admission, the autoscale /
backpressure controller (HTTP 429 deferrals are retried, counted),
p50/p99 latency and queue-depth/occupancy trajectories — the serving
stack exercised end-to-end as a service, with the same parity + ledger
gates as the batch-queue mode.

Prints ONE JSON line and writes it to SERVE_r<NN>.json
(next free round number; SERVE_OUT overrides).  Knobs: SERVE_TENANTS
(default 8), SERVE_CYCLES (default 3), SERVE_SLOTS (slots/bucket,
default 2 so slot recycling is exercised), SERVE_CHUNK (default 1),
PARMMG_SERVE_STREAM_RATE (default 2/sec, --stream only).
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# CPU backend (ledger_check.py sequence): the serving datapoint is a
# CPU-backend artifact until a chip session measures the path
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# cold = honest warmup
from parmmg_tpu.utils.compilecache import disable_persistent_cache  # noqa: E402
disable_persistent_cache()

import numpy as np  # noqa: E402


def _tenant(n: int, h: float):
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.utils.fixtures import analytic_iso_metric, cube_mesh

    vert, tet = cube_mesh(n)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    hh = analytic_iso_metric(vert, "shock", h=h)
    met = jnp.zeros(m.capP, m.vert.dtype).at[: len(hh)].set(
        jnp.asarray(hh, m.vert.dtype)).at[len(hh):].set(1.0)
    return m, met


def _tenant_raw(n: int, h: float):
    """Raw (vert, tet, met) for the daemon path: the daemon's
    stage_arrays applied to these reproduces _tenant() bit-for-bit
    (same 4x caps, same full-capP metric with unit pads)."""
    from parmmg_tpu.utils.fixtures import analytic_iso_metric, cube_mesh

    vert, tet = cube_mesh(n)
    hh = np.asarray(analytic_iso_metric(vert, "shock", h=h))
    met = np.ones(4 * len(vert), np.float64)
    met[: len(hh)] = hh
    return vert, tet, met


def main() -> int:
    from parmmg_tpu.core.mesh import MESH_FIELDS
    from parmmg_tpu.ops.quality import quality_histogram, tet_quality
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.serve.driver import ServeDriver
    from parmmg_tpu.utils.compilecache import (
        ledger_snapshot, regressions_vs_latest_artifact,
        variants_by_prefix)

    stream = "--stream" in sys.argv[1:]
    ntenants = int(os.environ.get("SERVE_TENANTS", "8"))
    cycles = int(os.environ.get("SERVE_CYCLES", "3"))
    slots = int(os.environ.get("SERVE_SLOTS", "2"))
    chunk = int(os.environ.get("SERVE_CHUNK", "1"))

    # three size classes -> three distinct capacity-ladder buckets
    classes = [("small", 2, 0.55), ("medium", 3, 0.45),
               ("large", 4, 0.60)]

    # ---- phase 1: batch warmup (+ parity reference) ----------------------
    warm = {}
    warm_s = {}
    for name, n, h in classes:
        m, met = _tenant(n, h)
        t0 = time.perf_counter()
        out, met_m, _ = grouped_adapt_pass(m, met, 1, cycles=cycles)
        jax.block_until_ready(out.vert)
        warm_s[name] = round(time.perf_counter() - t0, 2)
        q = np.asarray(tet_quality(out, met_m))[np.asarray(out.tmask)]
        warm[name] = (out, met_m, float(q.min()), float(q.mean()))
        print(f"serve_bench: warmup {name} (cube {n}, h={h}): "
              f"{warm_s[name]}s batch", file=sys.stderr)

    def grp_variants():
        return variants_by_prefix("groups.")

    v_batch = grp_variants()

    # ---- phase 2: serve N tenants through one warm pool ------------------
    daemon = cl = None
    stream_extra = None
    tenants = []
    if stream:
        # SERVE_r02 mode: open-loop arrivals through the pool DAEMON
        # over localhost HTTP (in-process ephemeral port — the ledger
        # diff below still sees every compile the daemon pays)
        from parmmg_tpu.serve.client import (BackpressureDeferred,
                                             ServeClient)
        from parmmg_tpu.serve.daemon import PoolDaemon
        rate = float(os.environ.get("PARMMG_SERVE_STREAM_RATE", "")
                     or 2.0)
        daemon = PoolDaemon(port=0, slots_per_bucket=slots, chunk=chunk,
                            cycles=cycles, verbose=1)
        daemon.start()
        drv = daemon.driver
        cl = ServeClient(port=daemon.port)
        arrivals = []
        for i in range(ntenants):
            name, n, h = classes[i % len(classes)]
            tid = f"{name}{i:02d}"
            arrivals.append([i / rate, tid] + list(_tenant_raw(n, h)))
            tenants.append((tid, name))
        submitted: set = set()
        terminal: set = set()
        deferred = 0
        traj = []
        t0 = time.perf_counter()
        while len(terminal) < ntenants:
            now = time.perf_counter() - t0
            while arrivals and arrivals[0][0] <= now:
                _due, tid, vert, tet, met = arrivals[0]
                try:
                    cl.submit(vert=vert, tet=tet, met=met, tenant=tid)
                    submitted.add(tid)
                    arrivals.pop(0)
                except BackpressureDeferred:
                    deferred += 1       # open-loop: retry shortly
                    arrivals[0][0] = now + 0.1
                    break
            for tid in sorted(submitted - terminal):
                if cl.poll(tid)["state"] not in ("queued", "running"):
                    terminal.add(tid)
            with daemon._lock:
                traj.append({
                    "t": round(now, 3),
                    "queue_depth": len(drv.queue),
                    "active": len(drv.pool.active_tenants()),
                    "occupancy": {k: list(v) for k, v in
                                  drv.pool.occupancy().items()}})
            time.sleep(0.05)
        serve_s = time.perf_counter() - t0
        with daemon._lock:
            rep = drv.report(list(drv._occupancy_traj))
        stream_extra = {
            "rate_per_s": rate,
            "deferred_submits": deferred,
            "stream_admissions": rep["admission"]["stream_admissions"],
            "autoscale": rep["autoscale"],
            "port": daemon.port,
            "traj": traj[:: max(1, len(traj) // 200)],
        }
    else:
        drv = ServeDriver(slots_per_bucket=slots, chunk=chunk,
                          cycles=cycles, verbose=1)
        for i in range(ntenants):
            name, n, h = classes[i % len(classes)]
            m, met = _tenant(n, h)
            tid = drv.submit(mesh=m, met=met, tenant=f"{name}{i:02d}")
            tenants.append((tid, name))
        t0 = time.perf_counter()
        rep = drv.run()
        serve_s = time.perf_counter() - t0

    v_serve = grp_variants()
    regressions = [f"{k}: {v_batch.get(k, 0)} -> {v}"
                   for k, v in sorted(v_serve.items())
                   if v > v_batch.get(k, 0)]

    # ---- phase 3: parity — one tenant per class vs its standalone run ----
    def fetch_arrays(tid):
        if stream:
            return cl.fetch(tid)
        mesh, met_m = drv.fetch(tid)
        out = {f: np.asarray(getattr(mesh, f)) for f in MESH_FIELDS}
        out["met"] = np.asarray(met_m)
        return out

    parity_ok = True
    seen = set()
    for tid, name in tenants:
        if name in seen:
            continue
        seen.add(name)
        try:
            arrays = fetch_arrays(tid)
        except Exception as e:
            parity_ok = False
            print(f"serve_bench: PARITY FETCH FAILED {tid}: {e!r}",
                  file=sys.stderr)
            continue
        ref, kref = warm[name][0], warm[name][1]
        for f in MESH_FIELDS:
            if not (arrays[f] == np.asarray(getattr(ref, f))).all():
                parity_ok = False
                print(f"serve_bench: PARITY MISMATCH {tid} field {f}",
                      file=sys.stderr)
        if not (arrays["met"] == np.asarray(kref)).all():
            parity_ok = False
            print(f"serve_bench: PARITY MISMATCH {tid} metric",
                  file=sys.stderr)
    if daemon is not None:
        daemon.shutdown()

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    ledger = ledger_snapshot()
    cross = regressions_vs_latest_artifact(root, "SERVE_r*.json", ledger)

    per_tenant = {
        tid: {
            "class": name,
            "state": rep["tenants"][tid]["state"],
            "latency_s": rep["tenants"][tid]["latency_s"],
            "qmin": (rep["tenants"][tid]["quality"] or {}).get("qmin"),
            "qmean": (rep["tenants"][tid]["quality"] or {}).get("qmean"),
            "ntets": (rep["tenants"][tid]["quality"] or {}).get("ntets"),
            "ops": rep["tenants"][tid]["ops"],
            "slo": rep["tenants"][tid].get("slo"),
        } for tid, name in tenants}

    # canonical schema-versioned artifact (obs/artifact.py)
    from parmmg_tpu.obs.artifact import make_artifact
    doc = make_artifact(
        "SERVE",
        metric="serve_throughput",
        value=round(rep["served"] / max(serve_s, 1e-9), 3),
        unit="meshes/sec (warm pool, CPU backend)",
        extra={
            "mode": "stream-daemon" if stream else "batch-queue",
            "tenants": ntenants,
            "served": rep["served"],
            "rejected": rep["rejected"],
            "failed": rep["failed"],
            "bucket_sizes": sorted({f"{k[0]}x{k[1]}" for k in
                                    drv.pool.buckets}),
            "cycles": cycles,
            "chunk": chunk,
            "slots_per_bucket": slots,
            "serve_wall_s": round(serve_s, 3),
            "warmup_batch_s": warm_s,
            "latency_p50_s": rep["latency_p50_s"],
            "latency_p90_s": rep["latency_p90_s"],
            "latency_p99_s": rep["latency_p99_s"],
            "latency_max_s": rep["latency_max_s"],
            "admission": rep["admission"],
            "stream": stream_extra,
            "per_tenant": per_tenant,
            "slot_occupancy": rep["occupancy_traj"],
            "active_per_step": rep["pool"]["active_per_step"],
            "dispatches": rep["pool"]["dispatches"],
            "chunk_recommendation": rep["pool"]["chunk_recommendation"],
            "pipeline_s": rep["pool"]["pipeline_s"],
            "parity_ok": parity_ok,
            "groups_variants_batch": v_batch,
            "groups_variants_serve": v_serve,
            "ledger_regressions": regressions,
            "ledger_regressions_vs_artifact": cross,
            "compile_ledger": ledger,
            "device": jax.default_backend(),
        })
    line = json.dumps(doc)
    print(line)

    out = os.environ.get("SERVE_OUT")
    if not out:
        nums = [int(m.group(1)) for p in glob.glob(
            os.path.join(root, "SERVE_r*.json"))
            if (m := re.search(r"r(\d+)\.json$", p))]
        out = os.path.join(root, f"SERVE_r{max(nums, default=0) + 1:02d}"
                                 ".json")
    with open(out, "w") as f:
        f.write(line + "\n")
    print(f"serve_bench: wrote {out}", file=sys.stderr)
    if regressions or not parity_ok:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
