"""Multi-host pod runner (multi-host step 2: the pod runtime).

Spawns NP jax.distributed processes on this host (virtual CPU devices,
``xla_force_host_platform_device_count``; cross-process collectives via
gloo, knob PARMMG_MH_COLLECTIVES), each running the IDENTICAL
``distributed_adapt_multi`` driver on the same input — the SPMD host
idiom of the reference's MPI program.  Band-table replication rides the
pod runtime's compiled exchange (``pod.gather_band``); the hot loop is
asserted allgather-free (``mh.hot_allgather_bytes == 0``).

Phase structure (the parent process):

1. ``--parity``: a single-process REFERENCE run of the same scenario in
   its own subprocess — the bit-parity oracle for ``extra.parity_ok``
   (and the 1-process seconds datapoint).
2. warm: unless the shared compile cache (PARMMG_MH_CACHE_DIR, default
   ``<repo>/.jax_cache_mh``) already holds this scenario's programs
   (marker file), run the NP-process scenario once to populate it —
   the one concurrent-compile cost (the whole MULTIHOST2P_r04 656 s
   story), paid once per scenario per cache.
3. timed run: NP processes over the warm cache.  Process 0 emits the
   canonical MULTIHOST artifact (obs/artifact.py) with per-phase trace
   spans; EVERY worker reports seconds / result hash / backend-compile
   seconds / ``mh.*`` counters through a JSON sidecar the parent merges
   into ``extra.workers`` — the "worker N+1 pays ~zero compiles"
   evidence.

Worker crash is the EXPECTED failure mode at pod scale: on a non-zero
worker exit the parent kills the survivors (a dead rank stalls the
collectives) and, when ``--ckpt`` is set, relaunches the run with
``resume=True`` — it re-enters at the pass after the newest per-pass
checkpoint and must finish bit-identical (`scripts/multihost_check.py`
asserts it).

A wedged worker is the same failure without the exit code: under
``--lease S`` each worker beats a per-rank heartbeat file (inside
``multihost.hot_path`` / ``pod.gather_band``; knob PARMMG_HEARTBEAT_S)
and the parent holds a lease per worker — a rank that has beaten once
and then goes silent for S seconds gets the whole pack killed (rc 9)
and the same checkpoint/resume relaunch.  A rank that never beat is
never stale: startup + cold compile are covered by ``--timeout``.

Usage: python scripts/multihost_run.py [--np 2] [--devices 4] [--n 4]
           [--niter 2] [--cycles 4] [--parity] [--no-warm]
           [--cache DIR] [--ckpt DIR] [--lease S]
           [--fault PID:SPEC] [--out PATH]
Prints ONE canonical artifact JSON line (stdout) from the parent.

Kept out of the default test matrix: ``run_tests.sh --multihost``
(scripts/multihost_check.py) runs the gated small scenario.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------
def worker() -> None:
    import numpy as np
    import jax

    pid = int(os.environ.get("JAX_PROCESS_ID", "0"))
    np_proc = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    n = int(os.environ["MH_N"])
    ndev = int(os.environ["MH_DEVICES"])
    niter = int(os.environ.get("MH_NITER", "2"))
    cycles = int(os.environ.get("MH_CYCLES", "4"))
    resume = os.environ.get("MH_RESUME", "") == "1"
    log = open(f"/tmp/parmmg_mh_{pid}.log", "w")

    def say(msg):
        print(msg, file=log, flush=True)
        if pid == 0:
            print(msg, file=sys.stderr, flush=True)

    t0 = time.time()
    from parmmg_tpu.parallel.multihost import init_multihost
    inited = init_multihost()
    if np_proc > 1:
        assert inited, "jax.distributed must initialize"
    say(f"[p{pid}] initialized: {jax.process_count()} processes, "
        f"{jax.device_count()} global / {jax.local_device_count()} "
        f"local devices ({time.time() - t0:.1f}s)")
    assert jax.process_count() == np_proc

    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import MESH_FIELDS, make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.ops.quality import tet_quality
    from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric
    from parmmg_tpu.parallel.dist import distributed_adapt_multi

    # identical input on every process (the deterministic-host contract)
    vert, tet = cube_mesh(n)
    mesh = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.8 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)
    say(f"[p{pid}] input: {len(tet)} tets -> {ndev} shards on "
        f"{np_proc} processes")

    t1 = time.time()
    out, met_m, part = distributed_adapt_multi(
        mesh, met, ndev, niter=niter, cycles=cycles, verbose=2,
        ckpt_tag=("mh" if os.environ.get("PARMMG_CKPT_DIR") else None),
        resume=resume)
    dt = time.time() - t1
    tm = np.asarray(out.tmask)
    q = np.asarray(tet_quality(out, met_m))[tm]
    hsh = hashlib.blake2b(digest_size=16)
    for f in MESH_FIELDS:
        hsh.update(np.ascontiguousarray(np.asarray(getattr(out, f)))
                   .tobytes())
    hsh.update(np.ascontiguousarray(np.asarray(met_m)).tobytes())
    digest = hsh.hexdigest()

    from parmmg_tpu.obs.metrics import REGISTRY
    from parmmg_tpu.utils.compilecache import LEDGER
    snap = LEDGER.snapshot()
    counters = REGISTRY.snapshot()["counters"]
    wrk = {
        "pid": pid,
        "seconds": round(dt, 1),
        "hash": digest,
        "compiles": int(sum(r["compiles"] for r in snap.values())),
        "compile_s": round(sum(r["compile_s"] for r in snap.values()),
                           2),
        "hot_allgather_bytes": counters.get("mh.hot_allgather_bytes",
                                            0),
        "allgather_bytes": counters.get("mh.allgather_bytes", 0),
        "band_exchange_bytes": counters.get("mh.band_exchange_bytes",
                                            0),
    }
    side = os.environ.get("MH_SIDECAR", "")
    if side:
        with open(side, "w") as f:
            json.dump(wrk, f)
    res = {
        "processes": np_proc,
        "devices": ndev,
        "ntets_in": int(len(tet)),
        "ntets_out": int(tm.sum()),
        "qmin": round(float(q.min()), 4),
        "qmean": round(float(q.mean()), 4),
        "niter": niter,
        "seconds": round(dt, 1),
        "hash": digest,
        "resumed": bool(resume),
        "pipeline": "split->adapt->band-exchange-migrate->weld->merge",
    }
    say(f"[p{pid}] done: {json.dumps(res)}")
    if pid == 0:
        # canonical schema-versioned artifact (obs/artifact.py) — the
        # legacy result dict rides in extra; per-phase spans ride the
        # trace digest (dist split/block/refresh/displace/migrate/merge)
        from parmmg_tpu.obs.artifact import make_artifact
        print(json.dumps(make_artifact(
            "MULTIHOST", metric="multihost_adapt",
            value=res["seconds"], unit="s", extra=res)))
    log.close()


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------
def launch(args, np_proc: int, tmpdir: str, resume: bool = False,
           fault: tuple[int, str] | None = None,
           tag: str = "run") -> tuple[int, bytes, list, dict]:
    """One phase: spawn np_proc workers, kill the pack on the first
    non-zero exit (a dead rank stalls the survivors' collectives) OR
    on an expired heartbeat lease (--lease: a WEDGED rank stalls them
    just the same, without the courtesy of exiting), return (rc,
    proc-0 stdout, worker sidecars, supervision info)."""
    # stdlib-only module (resilience/watchdog.py): safe in this parent
    # process, which must never import jax
    from parmmg_tpu.resilience.watchdog import stale_ranks
    port = free_port()
    procs = []
    sidecars = []
    info: dict = {}
    lease = float(getattr(args, "lease", 0) or 0)
    hb_dir = os.path.join(tmpdir, f"hb.{tag}")
    for pid in range(np_proc):
        side = os.path.join(tmpdir, f"{tag}.w{pid}.json")
        sidecars.append(side)
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (env.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count="
                          f"{args.devices // np_proc}").strip(),
            "MH_WORKER": "1",
            "MH_N": str(args.n),
            "MH_DEVICES": str(args.devices),
            "MH_NITER": str(args.niter),
            "MH_CYCLES": str(args.cycles),
            "MH_SIDECAR": side,
            "PARMMG_MH_CACHE_DIR": args.cache,
            # workers import the checkout, nothing else
            "PYTHONPATH": _repo_root(),
        })
        if np_proc > 1:
            env.update({
                "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
                "JAX_NUM_PROCESSES": str(np_proc),
                "JAX_PROCESS_ID": str(pid),
            })
        else:
            env["JAX_NUM_PROCESSES"] = "1"
            env.pop("JAX_COORDINATOR_ADDRESS", None)
        if args.ckpt:
            env["PARMMG_CKPT_DIR"] = args.ckpt
        if resume:
            env["MH_RESUME"] = "1"
        if lease > 0:
            # arm the per-rank heartbeat files this supervisor's lease
            # reads (workers beat inside hot_path / gather_band)
            env["PARMMG_MH_HEARTBEAT_DIR"] = hb_dir
        if fault is not None and fault[0] == pid:
            env["PARMMG_FAULT"] = fault[1]
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE if pid == 0 else subprocess.DEVNULL,
            stderr=sys.stderr if (pid == 0 and args.verbose)
            else subprocess.DEVNULL))
    rc = 0
    deadline = time.time() + args.timeout
    live = set(range(np_proc))
    failed = False
    while live and time.time() < deadline:
        for pid in sorted(live):
            r = procs[pid].poll()
            if r is None:
                continue
            live.discard(pid)
            if r != 0:
                rc = rc or r
                failed = True
        if not failed and lease > 0 and live:
            stale = stale_ranks(hb_dir, lease, sorted(live))
            if stale:
                # a WEDGED rank is a crashed rank that forgot to exit:
                # its lease expired (no beat for --lease seconds after
                # its FIRST beat), so treat it exactly like a non-zero
                # exit — kill the pack, let the checkpoint/resume
                # ladder recover
                print(f"multihost_run: heartbeat lease expired for "
                      f"rank(s) {stale} ({tag}); killing the pack",
                      file=sys.stderr)
                info["stale_heartbeat"] = stale
                rc = rc or 9
                failed = True
        if failed and live:
            # a dead rank stalls the survivors' collectives: kill the
            # pack (the checkpoint/resume ladder is the recovery, not
            # waiting out a gloo timeout)
            time.sleep(2)
            for pid in sorted(live):
                procs[pid].kill()
        time.sleep(0.2)
    if live:
        for pid in sorted(live):
            procs[pid].kill()
        print(f"multihost_run: TIMEOUT ({tag})", file=sys.stderr)
        rc = rc or 2
    out0 = b""
    if procs[0].stdout is not None:
        out0 = procs[0].stdout.read() or b""
        procs[0].stdout.close()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
    return (rc, out0,
            [json.load(open(s)) if os.path.exists(s) else None
             for s in sidecars], info)


def warm_marker(args) -> str:
    return os.path.join(
        args.cache,
        f"warm.np{args.np}.d{args.devices}.n{args.n}"
        f".i{args.niter}.c{args.cycles}.ok")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--np", type=int, default=2)
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--niter", type=int, default=2)
    ap.add_argument("--cycles", type=int, default=4)
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--cache", default=os.environ.get(
        "PARMMG_MH_CACHE_DIR",
        os.path.join(_repo_root(), ".jax_cache_mh")))
    ap.add_argument("--ckpt", default="",
                    help="per-pass checkpoint dir (arms resume-on-"
                         "crash)")
    ap.add_argument("--lease", type=float,
                    default=float(os.environ.get(
                        "PARMMG_HEARTBEAT_LEASE_S", "0") or 0),
                    help="heartbeat lease seconds: kill the pack when "
                         "a worker that already beat stops beating "
                         "this long (0 = off)")
    ap.add_argument("--parity", action="store_true",
                    help="run the 1-process reference for parity_ok")
    ap.add_argument("--no-warm", action="store_true")
    ap.add_argument("--fault", default="",
                    help="PID:SPEC — arm PARMMG_FAULT=SPEC in that "
                         "worker only (crash drill)")
    ap.add_argument("--out", default="")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()
    os.makedirs(args.cache, exist_ok=True)
    tmpdir = tempfile.mkdtemp(prefix="parmmg_mh_")
    fault = None
    if args.fault:
        fpid, _, spec = args.fault.partition(":")
        fault = (int(fpid), spec)
    extra_parent: dict = {"cache_dir": args.cache}

    # ---- phase 1: 1-process parity reference ---------------------------
    ref_hash = None
    if args.parity:
        t0 = time.time()
        rc, out0, sides, _info = launch(args, 1, tmpdir, tag="ref")
        if rc != 0:
            print("multihost_run: reference run failed", file=sys.stderr)
            sys.exit(rc)
        ref = json.loads(out0.decode().strip().splitlines()[-1])
        ref_hash = ref["extra"]["hash"]
        extra_parent["ref_seconds"] = ref["extra"]["seconds"]
        extra_parent["ref_wall_s"] = round(time.time() - t0, 1)

    # ---- phase 2: warm the shared compile cache ------------------------
    marker = warm_marker(args)
    if not args.no_warm and not os.path.exists(marker):
        t0 = time.time()
        rc, _out, _s, _info = launch(args, args.np, tmpdir, tag="warm")
        if rc != 0:
            print("multihost_run: warm run failed", file=sys.stderr)
            sys.exit(rc)
        extra_parent["warm_s"] = round(time.time() - t0, 1)
        with open(marker, "w") as f:
            f.write("ok\n")

    # ---- phase 3: the timed pod run ------------------------------------
    t0 = time.time()
    rc, out0, sides, info = launch(args, args.np, tmpdir, fault=fault,
                                   tag="timed")
    if info.get("stale_heartbeat"):
        extra_parent["stale_heartbeat"] = info["stale_heartbeat"]
    if rc != 0 and args.ckpt:
        # worker crash drill: the EXPECTED pod failure mode — relaunch
        # from the newest per-pass checkpoint (fault disarmed: the
        # crash — or the lease-expiry pack kill — consumed it)
        extra_parent["crashed_rc"] = rc
        rc, out0, sides, _info = launch(args, args.np, tmpdir,
                                        resume=True, tag="resumed")
    if rc != 0:
        print("multihost_run: FAILED", file=sys.stderr)
        sys.exit(rc)
    doc = json.loads(out0.decode().strip().splitlines()[-1])
    doc["extra"]["wall_s"] = round(time.time() - t0, 1)
    doc["extra"]["workers"] = [s for s in sides if s]
    doc["extra"].update(extra_parent)
    if ref_hash is not None:
        doc["extra"]["parity_ok"] = bool(
            doc["extra"]["hash"] == ref_hash)
    # cross-artifact regression diff vs the newest MULTIHOST round of
    # the SAME scenario (a gate-sized run must not diff its ledger
    # against the big-toy artifact — different scenarios legitimately
    # compile different variant counts)
    import glob
    import re

    def rnum(p: str) -> int:
        m = re.search(r"r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    regs: list = []
    doc["extra"]["ledger_diff_vs"] = None
    arts = sorted(glob.glob(os.path.join(_repo_root(),
                                         "MULTIHOST2P_r*.json")),
                  key=rnum, reverse=True)
    for prev_path in arts:
        try:
            with open(prev_path) as f:
                prev = json.load(f)
        except Exception:
            continue
        pex = prev.get("extra", prev)
        if any(pex.get(k) != doc["extra"].get(k)
               for k in ("processes", "devices", "ntets_in", "niter")):
            continue
        from parmmg_tpu.utils.compilecache import (
            extract_artifact_ledger, ledger_diff)
        regs = ledger_diff(extract_artifact_ledger(prev),
                           doc["extra"].get("compile_ledger", {}))
        doc["extra"]["ledger_diff_vs"] = os.path.basename(prev_path)
        break
    doc["extra"]["ledger_regressions"] = regs
    payload = json.dumps(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
    sys.stdout.write(payload + "\n")


if __name__ == "__main__":
    if os.environ.get("MH_WORKER") == "1":
        worker()
    else:
        main()
