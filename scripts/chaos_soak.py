"""Seeded chaos-soak harness (run_tests.sh --chaos rides a smoke of it).

``chaos_check.py`` proves each fault site lands on its documented
ladder rung ONCE, in a hand-picked order.  Production failure is not
hand-picked: faults arrive in random sites, random order, crash and
hang shapes mixed.  This harness drives N seeded runs, each with a
fault schedule drawn from the FULL ``faults.SITES`` registry
(including the PR 15 ``hang=S`` action, watchdog-deadline armed), and
asserts the bounded-time graded-failure contract per run:

- the run ends (no hang escapes the watchdog/timeout net) in either
  full success or a clean ``PMMG_LOWFAILURE`` with a conforming mesh
  (positive volumes summing to the cube);
- BIT-PARITY with the fault-free oracle whenever the schedule's
  expectation is a bit-identical rung (transient retries,
  mh_allgather, halo_dense, host analysis) — degraded never means drifted;
- no leaked ``parmmg_*`` staging in the temp dir;
- ZERO new ``groups.*`` compile families after the fault-free warmup
  — injected faults must never key fresh programs.

The schedule is a PURE function of (seed, runs): ``build_schedule``
is stdlib-only and importable without jax (tier-1 determinism test),
so any soak failure replays exactly from its seed.

Usage: python scripts/chaos_soak.py [--runs N] [--seed S] [--out PATH]
Knobs: PARMMG_SOAK_RUNS / PARMMG_SOAK_SEED (CLI defaults).
Prints ONE canonical SOAK artifact JSON line; exit 1 on any failure.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
from contextlib import contextmanager

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TARGET = 16          # cube_mesh(2) = 48 tets -> 3 groups
CYCLES = 2
NITER = 2

# ---------------------------------------------------------------------------
# the pure schedule builder (stdlib-only: no jax, no numpy)
# ---------------------------------------------------------------------------
# expectation vocabulary:
#   parity      — bit-identical to the runner's fault-free oracle
#   lowfailure  — driver returns PMMG_LOWFAILURE with a conforming mesh
#   quarantine  — tenant t1 retired FAILED; cohort-mates bit-identical
_MENU: tuple[dict, ...] = (
    {"runner": "grouped", "site": "dispatch.chunk",
     "fault": "dispatch.chunk:nth-{n}",
     "env": {"PARMMG_RETRY_MAX": "2"}, "expect": "parity"},
    {"runner": "grouped", "site": "dispatch.chunk",
     "fault": "dispatch.chunk:every-{n1}",
     "env": {"PARMMG_RETRY_MAX": "2"}, "expect": "parity"},
    {"runner": "grouped", "site": "dispatch.chunk",
     "fault": "dispatch.chunk:hang=2;nth-1",
     "env": {"PARMMG_RETRY_MAX": "2",
             "PARMMG_DEADLINE_DISPATCH_S": "0.5",
             "PARMMG_DEADLINE_GRACE_S": "0"}, "expect": "parity"},
    {"runner": "driver", "site": "dispatch.chunk",
     "fault": "dispatch.chunk",
     "env": {"PARMMG_RETRY_MAX": "1"}, "expect": "lowfailure"},
    {"runner": "grouped_ckpt", "site": "io.checkpoint",
     "fault": "io.checkpoint",
     "env": {"PARMMG_RETRY_MAX": "2"}, "expect": "parity"},
    {"runner": "dist", "site": "multihost.exchange",
     "fault": "multihost.exchange:nth-{n}",
     "env": {"PARMMG_RETRY_MAX": "2"}, "expect": "parity"},
    {"runner": "dist", "site": "multihost.exchange",
     "fault": "multihost.exchange",
     "env": {"PARMMG_RETRY_MAX": "0"}, "expect": "parity"},
    {"runner": "dist", "site": "multihost.exchange",
     "fault": "multihost.exchange:hang=2;nth-1",
     "env": {"PARMMG_RETRY_MAX": "2",
             "PARMMG_DEADLINE_EXCHANGE_S": "0.5",
             "PARMMG_DEADLINE_GRACE_S": "0"}, "expect": "parity"},
    {"runner": "dist", "site": "analysis.ks_overflow",
     "fault": "analysis.ks_overflow:nth-{n}",
     "env": {}, "expect": "parity"},
    {"runner": "dist", "site": "halo.exchange",
     "fault": "halo.exchange:nth-1",
     "env": {"PARMMG_RETRY_MAX": "2"}, "expect": "parity"},
    {"runner": "serve", "site": "serve.slot_step",
     "fault": "serve.slot_step:key=t1;nth-1",
     "env": {"PARMMG_SERVE_MAX_RETRIES": "2"}, "expect": "parity"},
    {"runner": "serve", "site": "serve.slot_step",
     "fault": "serve.slot_step:key=t1",
     "env": {"PARMMG_SERVE_MAX_RETRIES": "2"}, "expect": "quarantine"},
    {"runner": "daemon", "site": "serve.daemon_rpc",
     "fault": "serve.daemon_rpc:key=t1",
     "env": {}, "expect": "quarantine"},
)


def sites_in_menu() -> tuple[str, ...]:
    return tuple(sorted({m["site"] for m in _MENU}))


def build_schedule(seed: int, runs: int) -> list[dict]:
    """The campaign plan: a pure function of (seed, runs).  Every
    iteration consumes exactly two rng draws, so schedules are stable
    under menu-order-preserving edits and trivially replayable."""
    rng = random.Random(int(seed))
    sched = []
    for i in range(int(runs)):
        t = rng.choice(_MENU)
        n = rng.randint(1, 3)
        sched.append({
            "run": i,
            "runner": t["runner"],
            "site": t["site"],
            "fault": t["fault"].format(n=n, n1=n + 1),
            "env": dict(t["env"]),
            "expect": t["expect"],
        })
    return sched


# ---------------------------------------------------------------------------
# campaign execution (jax from here on)
# ---------------------------------------------------------------------------
def setup_env() -> None:
    """Process env for an in-process campaign (idempotent; matches the
    chaos gate's setup so the soak smoke can ride its warm programs)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    from parmmg_tpu.utils.compilecache import disable_persistent_cache
    disable_persistent_cache()
    for k in ("PARMMG_FAULT", "PARMMG_CKPT_DIR", "PARMMG_TRACE"):
        os.environ.pop(k, None)
    os.environ["PARMMG_GROUP_CHUNK"] = "2"
    os.environ.setdefault("PARMMG_RETRY_BASE_S", "0")


@contextmanager
def _env(**kv):
    """Scoped env knobs + fault-registry reset on entry AND exit."""
    from parmmg_tpu.resilience.faults import FAULTS
    old = {k: os.environ.get(k) for k in kv}
    for k, v in kv.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    FAULTS.reset()
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        FAULTS.reset()


def run_campaign(seed: int, runs: int, say=print) -> dict:
    """Execute the seeded campaign; returns the SOAK artifact doc with
    ``extra.failures`` (empty == soak clean)."""
    setup_env()
    import numpy as np
    import jax.numpy as jnp

    from parmmg_tpu.api.parmesh import ParMesh
    from parmmg_tpu.core import constants as C
    from parmmg_tpu.core.mesh import MESH_FIELDS, make_mesh, tet_volumes
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.dist import distributed_adapt_multi
    from parmmg_tpu.parallel.groups import grouped_adapt
    from parmmg_tpu.serve.driver import ServeDriver
    from parmmg_tpu.utils.compilecache import variants_by_prefix
    from parmmg_tpu.utils.fixtures import cube_mesh

    def fresh_case():
        vert, tet = cube_mesh(2)
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.full(m.capP, 0.35, m.vert.dtype)
        return m, met

    def state_bytes(mesh, met):
        return tuple(np.asarray(getattr(mesh, f)).tobytes()
                     for f in MESH_FIELDS) + (np.asarray(met).tobytes(),)

    def run_grouped(**kw):
        m, met = fresh_case()
        out, met_m = grouped_adapt(m, met, TARGET, niter=NITER,
                                   cycles=CYCLES, **kw)
        return state_bytes(out, met_m)

    def run_dist():
        m, met = fresh_case()
        out, met_m, _ = distributed_adapt_multi(m, met, 2, niter=NITER,
                                                cycles=CYCLES)
        return state_bytes(out, met_m)

    def staged_pm():
        vert, tet = cube_mesh(2)
        pm = ParMesh()
        pm.set_mesh_size(len(vert), len(tet))
        pm.set_vertices(vert, np.zeros(len(vert), np.int32))
        pm.set_tetrahedra(tet + 1, np.ones(len(tet), np.int32))
        pm.info.hsiz = 0.35
        pm.info.niter = 1
        pm.info.imprim = -1
        pm.info.target_mesh_size = TARGET
        pm.info.noinsert = pm.info.noswap = pm.info.nomove = True
        return pm

    def conforming(mesh) -> bool:
        tm = np.asarray(mesh.tmask)
        vols = np.asarray(tet_volumes(mesh))[tm]
        return bool(tm.sum() > 0 and (vols > 0).all()
                    and np.isclose(vols.sum(), 1.0, rtol=1e-5))

    def run_pool():
        drv = ServeDriver(slots_per_bucket=3, chunk=2, cycles=CYCLES)
        for t in ("t0", "t1", "t2"):
            m, met = fresh_case()
            drv.submit(mesh=m, met=met, tenant=t)
        rep = drv.run()
        outs = {}
        for t in ("t0", "t1", "t2"):
            if rep["tenants"][t]["state"] == "done":
                outs[t] = state_bytes(*drv.fetch(t))
        return rep, outs

    def run_daemon(fault_spec):
        # the serve.daemon_rpc shape needs the HTTP edge: pause the
        # loop, admit 3 tenants, arm the fault around a mid-flight
        # poll of t1 (mirrors the chaos gate's scenario) — the daemon
        # must survive, t1 alone quarantined
        from parmmg_tpu.serve.client import ServeClient, ServeDaemonError
        from parmmg_tpu.serve.daemon import PoolDaemon
        vert, tet = cube_mesh(2)
        met_full = np.full(4 * len(vert), 0.35)
        d = PoolDaemon(port=0, slots_per_bucket=3, chunk=2,
                       cycles=CYCLES, start_paused=True)
        d.start()
        outs = {}
        probs = []
        try:
            cl = ServeClient(port=d.port)
            for t in ("t0", "t1", "t2"):
                cl.submit(vert=vert, tet=tet, met=met_full, tenant=t)
            cl.step()
            with _env(PARMMG_FAULT=fault_spec):
                try:
                    cl.poll("t1")
                    probs.append("armed daemon_rpc fault did not fire")
                except ServeDaemonError as e:
                    if not (e.status == 500
                            and e.body.get("quarantined") is True):
                        probs.append(f"rpc fault shape wrong: {e}")
            if cl.health().get("ok") is not True:
                probs.append("daemon died with the faulted request")
            cl.resume()
            for t in ("t0", "t2"):
                got = cl.wait(t, timeout_s=600)
                if got["state"] != "done":
                    probs.append(f"cohort tenant {t}: {got['state']}")
                    continue
                arrays = cl.fetch(t)
                outs[t] = tuple(arrays[f].tobytes()
                                for f in MESH_FIELDS) \
                    + (arrays["met"].tobytes(),)
            rep = cl.report()
            if rep["tenants"]["t1"]["state"] != "failed":
                probs.append("t1 not retired FAILED")
        finally:
            d.shutdown()
        return probs, outs

    # ---- fault-free warmup: every runner's oracle + compile baseline ---
    say(f"soak: warmup (oracles for {len(_MENU)} menu entries)")
    base_g = run_grouped()
    base_d = run_dist()
    pm0 = staged_pm()
    rc0 = pm0.run()
    assert rc0 == C.PMMG_SUCCESS, f"warmup driver run rc={rc0}"
    rep_a, outs_a = run_pool()
    assert rep_a["served"] == 3, "warmup pool must serve 3"
    def live_groups():
        # drop zero-variant keys: a runner REGISTERING a governed
        # family it never compiled is bookkeeping, not compile growth
        return {k: v for k, v in variants_by_prefix("groups.").items()
                if v}

    v0 = live_groups()
    tmp0 = {e for e in os.listdir(tempfile.gettempdir())
            if e.startswith("parmmg_")}
    oracles = {"grouped": base_g, "grouped_ckpt": base_g,
               "dist": base_d}

    sched = build_schedule(seed, runs)
    failures: list[str] = []
    records: list[dict] = []
    for spec in sched:
        tag = (f"run {spec['run']} [{spec['runner']}] "
               f"{spec['fault']} -> {spec['expect']}")
        say(f"soak: {tag}")
        probs: list[str] = []
        kv = dict(spec["env"])
        kv["PARMMG_FAULT"] = spec["fault"]
        try:
            if spec["runner"] in ("grouped", "dist"):
                with _env(**kv):
                    got = run_grouped() if spec["runner"] == "grouped" \
                        else run_dist()
                if got != oracles[spec["runner"]]:
                    probs.append("bit-parity with fault-free oracle")
            elif spec["runner"] == "grouped_ckpt":
                with tempfile.TemporaryDirectory() as td, \
                        _env(PARMMG_CKPT_DIR=td, **kv):
                    got = run_grouped(ckpt_tag=f"soak{spec['run']}")
                    left = [f for f in os.listdir(td)
                            if f.endswith(".npz")]
                if got != oracles["grouped_ckpt"]:
                    probs.append("bit-parity under checkpoint IO fault")
                if spec["site"] == "io.checkpoint" and left:
                    probs.append(f"partial checkpoint survived: {left}")
            elif spec["runner"] == "driver":
                with _env(**kv):
                    pm = staged_pm()
                    ret = pm.run()
                if ret != C.PMMG_LOWFAILURE:
                    probs.append(f"expected PMMG_LOWFAILURE, rc={ret}")
                elif not conforming(pm._out):
                    probs.append("LOWFAILURE output not conforming")
            elif spec["runner"] == "serve":
                with _env(**kv):
                    rep, outs = run_pool()
                if spec["expect"] == "parity":
                    if not (rep["served"] == 3 and outs == outs_a):
                        probs.append("transient serve fault parity")
                else:
                    if rep["tenants"]["t1"]["state"] != "failed":
                        probs.append("t1 not quarantined")
                    if not (outs.get("t0") == outs_a["t0"]
                            and outs.get("t2") == outs_a["t2"]):
                        probs.append("cohort parity after quarantine")
            elif spec["runner"] == "daemon":
                probs, outs = run_daemon(spec["fault"])
                if not (outs.get("t0") == outs_a["t0"]
                        and outs.get("t2") == outs_a["t2"]):
                    probs.append("daemon cohort parity")
            else:
                probs.append(f"unknown runner {spec['runner']!r}")
        except Exception as e:                    # noqa: BLE001
            probs.append(f"escaped exception {e!r:.300}")
        # per-run hygiene: staging leaks + compile-family neutrality
        leaks = [e for e in os.listdir(tempfile.gettempdir())
                 if e.startswith("parmmg_") and e not in tmp0]
        if leaks:
            probs.append(f"tmp leak {leaks}")
        v1 = live_groups()
        if v1 != v0:
            probs.append(f"new groups.* compile families {v0} -> {v1}")
            v0 = v1          # report each regression once
        records.append({**spec, "ok": not probs, "problems": probs})
        for p in probs:
            failures.append(f"{tag}: {p}")
            say(f"soak FAIL: {tag}: {p}")

    from parmmg_tpu.obs.artifact import make_artifact
    doc = make_artifact(
        "SOAK", metric="soak_runs", value=float(len(sched)),
        unit="runs",
        extra={
            "seed": int(seed),
            "runs": int(runs),
            "sites_covered": list(sites_in_menu()),
            "failed": len(failures),
            "failures": failures,
            "schedule": records,
        })
    return doc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=int(
        os.environ.get("PARMMG_SOAK_RUNS", "8") or 8))
    ap.add_argument("--seed", type=int, default=int(
        os.environ.get("PARMMG_SOAK_SEED", "20260804") or 20260804))
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    def say(msg):
        print(msg, file=sys.stderr, flush=True)

    doc = run_campaign(args.seed, args.runs, say=say)
    payload = json.dumps(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
    sys.stdout.write(payload + "\n")
    nfail = doc["extra"]["failed"]
    if nfail:
        say(f"soak FAILED: {nfail} problems over "
            f"{doc['extra']['runs']} runs (seed {doc['extra']['seed']})")
        return 1
    say(f"soak OK: {doc['extra']['runs']} seeded runs, "
        f"{len(doc['extra']['sites_covered'])} fault sites, zero "
        "escapes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
