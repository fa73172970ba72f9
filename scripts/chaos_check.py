"""Fault-injection gate (scripts/run_tests.sh --chaos).

Runs a small fault matrix IN-PROCESS on the CPU backend and FAILS
(exit 1) unless every injected fault lands on its documented
escalation-ladder step (resilience/recover.py):

1. **zero-fault neutrality**: a grouped run with the resilience wiring
   active (checkpointing armed, retry budget set) is BIT-IDENTICAL to
   the plain run and adds ZERO new ``groups.*`` compile-ledger
   families — resilience is host bookkeeping, never a new program;
2. **transient dispatch fault** (``dispatch.chunk:nth-1``): the chunk
   retries and the run recovers bit-for-bit (ladder step ``retry``);
3. **retry-budget exhaustion** (``dispatch.chunk`` every hit,
   ``PARMMG_RETRY_MAX=1``): the driver degrades to ``PMMG_LOWFAILURE``
   and the staged output is still a conforming mesh (ladder terminal
   ``lowfailure`` — the failed_handling contract);
5. **checkpoint/resume**: a run resumed from the last completed pass
   checkpoint finishes bit-identical to the uninterrupted run; an
   injected ``io.checkpoint`` OSError is absorbed (counter, no crash,
   bit-neutral);
6. **serve-pool quarantine** (``serve.slot_step;key=<tenant>``): a
   persistently faulting tenant is retired FAILED/quarantined while
   its cohort-mates retire bit-identical to a fault-free pool; a
   transient tenant fault recovers in-step with full parity;
7. **daemon RPC fault** (``serve.daemon_rpc:key=<tenant>``): an RPC
   handled for a mid-flight tenant dies; the DAEMON survives, that
   tenant alone is quarantined (retired FAILED, slot scrubbed +
   recycled) and cohort-mates retire bit-identical to the fault-free
   daemon and to the in-process pool.

CPU backend, persistent cache off (ledger_check.py sequence).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
from contextlib import contextmanager

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ["JAX_PLATFORMS"] = "cpu"
# 2 virtual devices: scenario 8 runs the 2-shard distributed path
# (multihost.exchange faultpoint); the grouped scenarios are
# single-device and unaffected by the extra virtual device
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()
for _k in ("PARMMG_FAULT", "PARMMG_CKPT_DIR", "PARMMG_TRACE"):
    os.environ.pop(_k, None)

import jax  # noqa: E402

from parmmg_tpu.utils.compilecache import disable_persistent_cache  # noqa: E402
disable_persistent_cache()

import numpy as np  # noqa: E402

# chunked dispatch everywhere: _pipeline_chunks (the dispatch.chunk
# site + retry path) only runs in chunk mode
os.environ["PARMMG_GROUP_CHUNK"] = "2"
os.environ.setdefault("PARMMG_RETRY_BASE_S", "0")

TARGET = 16          # cube_mesh(2) = 48 tets -> 3 groups
CYCLES = 2
NITER = 2


@contextmanager
def env(**kv):
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


def fresh_case():
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.utils.fixtures import cube_mesh
    vert, tet = cube_mesh(2)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, 0.35, m.vert.dtype)
    return m, met


def state_bytes(mesh, met):
    from parmmg_tpu.core.mesh import MESH_FIELDS
    return tuple(np.asarray(getattr(mesh, f)).tobytes()
                 for f in MESH_FIELDS) + (np.asarray(met).tobytes(),)


def run_grouped(**kw):
    from parmmg_tpu.parallel.groups import grouped_adapt
    m, met = fresh_case()
    out, met_m = grouped_adapt(m, met, TARGET, niter=NITER,
                               cycles=CYCLES, **kw)
    return state_bytes(out, met_m)


def counters():
    from parmmg_tpu.obs.metrics import REGISTRY
    return dict(REGISTRY.snapshot()["counters"])


def delta(before, name):
    return counters().get(name, 0) - before.get(name, 0)


def ladder_steps_since(mark):
    from parmmg_tpu.obs.trace import TRACER
    return [r.get("step") for r in list(TRACER.ring)[mark:]
            if r.get("kind") == "event"
            and r.get("name") == "resilience.ladder"]


def ring_mark():
    from parmmg_tpu.obs.trace import TRACER
    return len(TRACER.ring)


FAILS: list[str] = []


def check(ok: bool, msg: str) -> None:
    tag = "ok" if ok else "CHAOS FAIL"
    print(f"  {tag}: {msg}" if ok else f"{tag}: {msg}",
          file=sys.stdout if ok else sys.stderr)
    if not ok:
        FAILS.append(msg)


def main() -> int:
    from parmmg_tpu.utils.compilecache import (reset_ledger,
                                               variants_by_prefix)

    # ---- 0. spec grammar sanity (host only) ----------------------------
    from parmmg_tpu.resilience.faults import parse_fault_spec
    print("--- chaos gate: fault spec grammar")
    r = parse_fault_spec("dispatch.chunk:nth-2,serve.slot_step:"
                         "key=t1;every-3")
    check(r["dispatch.chunk"].nth == 2
          and r["serve.slot_step"].key == "t1"
          and r["serve.slot_step"].every == 3, "spec grammar parses")
    for bad in ("no.such.site", "dispatch.chunk:wat-3"):
        try:
            parse_fault_spec(bad)
            check(False, f"spec {bad!r} should have been rejected")
        except ValueError:
            check(True, f"spec {bad!r} rejected")

    # ---- 1. baseline + zero-fault neutrality ---------------------------
    print("--- chaos gate: zero-fault neutrality")
    reset_ledger()
    base = run_grouped()
    v0 = variants_by_prefix("groups.")
    check(v0.get("groups.adapt_block", 0) >= 1,
          "scenario exercises groups.adapt_block")
    with tempfile.TemporaryDirectory() as td, \
            env(PARMMG_CKPT_DIR=td, PARMMG_RETRY_MAX="2"):
        wired = run_grouped(ckpt_tag="neutral")
        ckpts = [f for f in os.listdir(td) if f.endswith(".npz")]
    v1 = variants_by_prefix("groups.")
    check(wired == base, "resilience wiring (ckpt+retry armed, zero "
                         "faults) is bit-neutral")
    check(v1 == v0, f"zero new groups.* compile families ({v0} -> {v1})")
    # every pass checkpoints, INCLUDING the final one (a kill during
    # the post-adapt tail must not restart the adaptation)
    check(len(ckpts) == NITER,
          f"pass checkpoints written ({ckpts})")

    # ---- 2. transient dispatch fault recovers bit-for-bit --------------
    print("--- chaos gate: dispatch.chunk transient fault")
    c0 = counters()
    mark = ring_mark()
    with env(PARMMG_FAULT="dispatch.chunk:nth-1", PARMMG_RETRY_MAX="2"):
        got = run_grouped()
    check(got == base, "nth-1 dispatch fault recovered bit-for-bit")
    check(delta(c0, "resilience.faults_injected") >= 1,
          "fault actually injected")
    check(delta(c0, "resilience.retry") >= 1, "retry rung recorded")
    check("retry" in ladder_steps_since(mark), "ladder event emitted")

    # ---- 3. retry exhaustion -> LOWFAILURE + conforming mesh -----------
    print("--- chaos gate: dispatch.chunk retry exhaustion")
    from parmmg_tpu.api.parmesh import ParMesh
    from parmmg_tpu.core import constants as C
    from parmmg_tpu.core.mesh import tet_volumes
    from parmmg_tpu.utils.fixtures import cube_mesh

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
        # no-op remesh switches: the fault fires before any cycle runs,
        # and the switches keep the degrade tail (repair/fem) off so
        # the gate stays cheap
        pm.info.noinsert = pm.info.noswap = pm.info.nomove = True
        return pm

    c0 = counters()
    with env(PARMMG_FAULT="dispatch.chunk", PARMMG_RETRY_MAX="1"):
        pm = staged_pm()
        ret = pm.run()
    check(ret == C.PMMG_LOWFAILURE,
          f"exhausted retries degrade to PMMG_LOWFAILURE (got {ret})")
    check(delta(c0, "resilience.retry_exhausted") >= 1,
          "retry budget exhaustion recorded")
    check(delta(c0, "resilience.lowfailure") >= 1,
          "lowfailure ladder terminal recorded")
    tm = np.asarray(pm._out.tmask)
    vols = np.asarray(tet_volumes(pm._out))[tm]
    check(tm.sum() > 0 and (vols > 0).all()
          and np.isclose(vols.sum(), 1.0, rtol=1e-5),
          "LOWFAILURE output is a conforming mesh (positive volumes "
          "summing to the cube)")

    # ---- 5. checkpoint/resume bit-identity -----------------------------
    print("--- chaos gate: checkpoint/resume")
    with tempfile.TemporaryDirectory() as td, env(PARMMG_CKPT_DIR=td):
        full = run_grouped(ckpt_tag="ck")
        shard_files = [f for f in os.listdir(td)
                       if f.startswith("ck.pass0") and f.endswith(".mesh")]
        check(len(shard_files) == 3,
              f"stacked_to_distributed_files snapshot written "
              f"({shard_files})")
        # "killed after pass 0": drop the final-pass checkpoint (the
        # kill happened before it), resume from pass 0's, re-run the
        # remaining pass — must land bit-identical to the full run
        os.unlink(os.path.join(td, f"ck.pass{NITER - 1}.npz"))
        c0 = counters()
        resumed = run_grouped(ckpt_tag="ck", resume=True)
        check(resumed == full, "resumed run is bit-identical to the "
                               "uninterrupted run")
        check(delta(c0, "resilience.resumes") == 1, "resume recorded")
    c0 = counters()
    with tempfile.TemporaryDirectory() as td, \
            env(PARMMG_CKPT_DIR=td, PARMMG_FAULT="io.checkpoint"):
        got = run_grouped(ckpt_tag="ckf")
        left = [f for f in os.listdir(td) if f.endswith(".npz")]
    check(got == base, "checkpoint IO fault is bit-neutral to the run")
    check(delta(c0, "resilience.checkpoint_failures") >= 1,
          "checkpoint_failures counter bumped")
    check(not left, f"no partial checkpoint survives the fault ({left})")

    # ---- 6. serve-pool quarantine + cohort parity ----------------------
    print("--- chaos gate: serve quarantine")
    from parmmg_tpu.serve.driver import ServeDriver

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

    rep_a, outs_a = run_pool()
    check(rep_a["served"] == 3, f"fault-free pool serves 3 ({rep_a['served']})")
    c0 = counters()
    with env(PARMMG_FAULT="serve.slot_step:key=t1",
             PARMMG_SERVE_MAX_RETRIES="2"):
        rep_b, outs_b = run_pool()
    check(rep_b["tenants"]["t1"]["state"] == "failed"
          and "quarantined" in rep_b["tenants"]["t1"]["reason"],
          f"poisoned tenant quarantined "
          f"({rep_b['tenants']['t1']['reason']!r})")
    check(rep_b["pool"]["quarantined"] == ["t1"],
          "quarantine visible in the pool report")
    check(delta(c0, "serve.quarantined") == 1,
          "serve.quarantined counter bumped")
    check(outs_b.get("t0") == outs_a["t0"]
          and outs_b.get("t2") == outs_a["t2"],
          "cohort-mates retire bit-identical to the fault-free pool")
    # transient tenant fault: in-step per-slot recovery, full parity
    with env(PARMMG_FAULT="serve.slot_step:key=t1;nth-1",
             PARMMG_SERVE_MAX_RETRIES="2"):
        rep_c, outs_c = run_pool()
    check(rep_c["served"] == 3 and outs_c == outs_a,
          "transient tenant fault recovers in-step with full parity")

    # ---- 7. daemon RPC fault -> mid-flight kill + quarantine -----------
    print("--- chaos gate: serve.daemon_rpc mid-flight kill")
    from parmmg_tpu.core.mesh import MESH_FIELDS
    from parmmg_tpu.serve.client import ServeClient, ServeDaemonError
    from parmmg_tpu.serve.daemon import PoolDaemon
    from parmmg_tpu.utils.fixtures import cube_mesh

    vert, tet = cube_mesh(2)
    met_full = np.full(4 * len(vert), 0.35)   # == fresh_case() staging

    def arrays_bytes(arrays):
        return tuple(arrays[f].tobytes() for f in MESH_FIELDS) \
            + (arrays["met"].tobytes(),)

    def run_daemon_pool(kill_t1: bool):
        d = PoolDaemon(port=0, slots_per_bucket=3, chunk=2,
                       cycles=CYCLES, start_paused=True)
        d.start()
        outs = {}
        rep = None
        try:
            cl = ServeClient(port=d.port)
            for t in ("t0", "t1", "t2"):
                cl.submit(vert=vert, tet=tet, met=met_full, tenant=t)
            cl.step()         # admits all 3 + advances one block each
            if kill_t1:
                check(cl.poll("t1")["state"] == "running",
                      "t1 is mid-flight (RUNNING) after one step")
                with env(PARMMG_FAULT="serve.daemon_rpc:key=t1"):
                    try:
                        cl.poll("t1")
                        check(False, "armed serve.daemon_rpc fault did "
                                     "not fire")
                    except ServeDaemonError as e:
                        check(e.status == 500
                              and e.body.get("quarantined") is True,
                              "RPC fault killed the in-flight request "
                              f"(HTTP {e.status}, tenant quarantined)")
                check(cl.health().get("ok") is True,
                      "daemon survives the RPC fault")
            cl.resume()
            for t in ("t0", "t2") + (() if kill_t1 else ("t1",)):
                got = cl.wait(t, timeout_s=600)
                check(got["state"] == "done",
                      f"daemon tenant {t} served ({got['state']})")
                outs[t] = arrays_bytes(cl.fetch(t))
            rep = cl.report()
        finally:
            d.shutdown()
        return rep, outs

    rep_d0, outs_d0 = run_daemon_pool(kill_t1=False)
    check(rep_d0["served"] == 3,
          f"fault-free daemon serves 3 ({rep_d0['served']})")
    check(all(outs_d0.get(t) == outs_a[t] for t in ("t0", "t1", "t2")),
          "daemon-served tenants bit-identical to the in-process pool")
    c0 = counters()
    rep_d1, outs_d1 = run_daemon_pool(kill_t1=True)
    check(rep_d1["tenants"]["t1"]["state"] == "failed"
          and "daemon rpc fault" in rep_d1["tenants"]["t1"]["reason"],
          "killed request retired FAILED "
          f"({rep_d1['tenants']['t1']['reason']!r})")
    check("t1" in rep_d1["pool"]["quarantined"],
          "RPC-edge quarantine visible in the pool report")
    check(delta(c0, "serve.quarantined") >= 1,
          "serve.quarantined counter bumped")
    check(delta(c0, "serve.rpc_faults") >= 1,
          "serve.rpc_faults counter bumped")
    check(outs_d1.get("t0") == outs_a["t0"]
          and outs_d1.get("t2") == outs_a["t2"],
          "cohort-mates of the killed request retire bit-identical")

    # ---- 8. multihost.exchange: band-exchange fault ladder -------------
    # (single-process arm of the pod failure semantics: transient ->
    # retry rung; exhausted -> mh_allgather escape hatch, both
    # bit-identical.  The cross-process arm — worker death -> resume
    # from the per-pass checkpoint — is run_tests.sh --multihost.)
    print("--- chaos gate: multihost.exchange band-exchange fault")
    from parmmg_tpu.parallel.dist import distributed_adapt_multi

    def run_dist():
        m, met = fresh_case()
        out, met_m, _ = distributed_adapt_multi(m, met, 2, niter=2,
                                                cycles=CYCLES)
        return state_bytes(out, met_m)

    base_d = run_dist()
    c0 = counters()
    mark = ring_mark()
    with env(PARMMG_FAULT="multihost.exchange:nth-1",
             PARMMG_RETRY_MAX="2"):
        got = run_dist()
    check(got == base_d,
          "nth-1 exchange fault recovered bit-for-bit (retry rung)")
    check(delta(c0, "resilience.faults_injected") >= 1,
          "exchange fault actually injected")
    check("retry" in ladder_steps_since(mark),
          "retry ladder event emitted")
    c0 = counters()
    mark = ring_mark()
    with env(PARMMG_FAULT="multihost.exchange", PARMMG_RETRY_MAX="0"):
        got2 = run_dist()
    check(got2 == base_d,
          "exhausted exchange degrades to the metered allgather "
          "bit-for-bit")
    check("mh_allgather" in ladder_steps_since(mark),
          "mh_allgather ladder step recorded")
    check(delta(c0, "resilience.mh_allgather") >= 1,
          "resilience.mh_allgather counter bumped")

    # ---- 9. hang drills: deadline watchdogs convert wedges to retries --
    # (PR 15: the hang=S fault action sleeps inside the site instead of
    # raising; only an armed PARMMG_DEADLINE_* watchdog can turn that
    # into the WatchdogTimeout the existing ladder already handles)
    print("--- chaos gate: hang=S -> deadline watchdog -> ladder")
    c0 = counters()
    mark = ring_mark()
    with env(PARMMG_FAULT="dispatch.chunk:hang=3;nth-1",
             PARMMG_RETRY_MAX="2", PARMMG_DEADLINE_DISPATCH_S="0.5",
             PARMMG_DEADLINE_GRACE_S="0"):
        got = run_grouped()
    check(got == base,
          "wedged chunk dispatch recovered bit-for-bit (watchdog -> "
          "retry rung)")
    check(delta(c0, "resilience.watchdog_timeouts") >= 1,
          "watchdog_timeouts counter bumped")
    check("retry" in ladder_steps_since(mark),
          "watchdog expiry entered the retry ladder")
    # wedged single-process band exchange -> watchdog -> retry rung
    c0 = counters()
    with env(PARMMG_FAULT="multihost.exchange:hang=3;nth-1",
             PARMMG_RETRY_MAX="2", PARMMG_DEADLINE_EXCHANGE_S="0.5",
             PARMMG_DEADLINE_GRACE_S="0"):
        got = run_dist()
    check(got == base_d,
          "wedged band exchange recovered bit-for-bit (watchdog -> "
          "retry rung)")
    check(delta(c0, "resilience.watchdog_timeouts") >= 1,
          "exchange watchdog expiry recorded")

    # ---- 10. seeded soak smoke (scripts/chaos_soak.py, in-process) -----
    # fixed seed, 3 runs: proves the harness end-to-end on the warm
    # programs this gate already compiled; the full campaign is the
    # standalone `python scripts/chaos_soak.py`
    print("--- chaos gate: seeded soak smoke (3 runs)")
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chaos_soak", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "chaos_soak.py"))
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)
    sched = soak.build_schedule(11, 3)
    check(sched == soak.build_schedule(11, 3)
          and sched != soak.build_schedule(12, 3),
          "soak schedule is a pure function of the seed")
    doc = soak.run_campaign(11, 3, say=lambda m: print(f"  {m}"))
    check(doc["extra"]["failed"] == 0,
          f"soak smoke clean ({doc['extra']['failures']})")
    check(doc["kind"] == "SOAK" and doc["extra"]["runs"] == 3,
          "soak artifact well-formed")

    # ---- verdict -------------------------------------------------------
    if FAILS:
        print(f"\nchaos gate FAILED ({len(FAILS)} checks):",
              file=sys.stderr)
        for f in FAILS:
            print(f"  {f}", file=sys.stderr)
        return 1
    print("\nchaos OK: every injected fault recovered bit-for-bit or "
          "degraded to its documented ladder step; fault-free "
          "resilience wiring is bit-neutral with zero new compile "
          "families")
    return 0


if __name__ == "__main__":
    sys.exit(main())
