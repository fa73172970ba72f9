"""Million-tet single-chip datapoint via the two-level group machinery.

The 10M-tet configuration (BASELINE.md planned configs) is reachable on
one chip only through sub-device groups: chunked ``lax.map`` over group
slots keeps the working set (and the O(n log^2 n) wave sorts) at GROUP
size while HOST RAM holds the whole mesh (parallel/groups.py, the
grpsplit_pmmg.c:1551 role).  This script runs grouped adaptation passes
on a >=1M-tet shock cube and reports per-phase timings + the grouped
throughput as ONE JSON line.

Process layout: each grouped PASS runs in its own subprocess
(SCALE_WORKER=1 re-entry), with the merged mesh handed over via .npz.
A chip belongs to one process at a time, so this parent-plus-workers
layout is a CPU-only tool: the script refuses to run unless the caller
set JAX_PLATFORMS=cpu, and the workers inherit the pin.  The chip run
of the grouped path is one process: ``python chip_smoke.py`` (or the
CLI).

Run: JAX_PLATFORMS=cpu python scripts/scale_big.py
Knobs: SCALE_N (default 56 -> 6*56^3 = 1,053,696 tets),
       SCALE_TARGET (group size target, default 24576),
       SCALE_CYCLES (default 6), SCALE_NITER (passes, default 2).

Resume (``--resume`` / SCALE_RESUME=1): the per-pass ``state<k>.npz``
hand-over files under SCALE_TMP double as pass checkpoints — each gets
a ``.ok`` marker only once it is a COMPLETE pass input (state0 after
staging, state<k> after the displacement rewrite), so a kill mid-pass
or mid-write can never leave a marked-but-corrupt state.  A resumed
run restarts from the newest marked state and, passes being
deterministic functions of their input state, finishes bit-identical
to an uninterrupted run (the resilience/checkpoint.py contract; the
in-process half is chaos-gated by scripts/chaos_check.py).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from parmmg_tpu.core.mesh import MESH_FIELDS
from parmmg_tpu.utils.compilecache import ledger_snapshot, set_cache_env

# persistent compile cache shared with the CLI/bench (compile governor):
# env-only here so the pass workers inherit it — that is what stops
# every worker subprocess recompiling the grouped programs from scratch
# (set_cache_env declines on the pinned CPU backend unless the caller
# opted in with JAX_COMPILATION_CACHE_DIR)
set_cache_env()


def _save_state(path, mesh, met, part, extra=None):
    np.savez(path, met=np.asarray(met), part=np.asarray(part),
             **{f: np.asarray(getattr(mesh, f)) for f in MESH_FIELDS},
             **(extra or {}))


def _load_state(path):
    from parmmg_tpu.core.mesh import Mesh
    z = np.load(path)
    mesh = Mesh(**{f: z[f] for f in MESH_FIELDS})
    return z, mesh, z["met"], z["part"]


def _mark_ready(path: str) -> None:
    """Completion marker: ``path`` is a complete pass-input state."""
    with open(path + ".ok", "w") as f:
        f.write("ok\n")


def _find_resume(tmp: str, niter: int) -> int | None:
    """Newest pass index k whose state<k>.npz is marked complete."""
    best = None
    for k in range(niter + 1):
        p = f"{tmp}/state{k}.npz"
        if os.path.exists(p) and os.path.exists(p + ".ok"):
            best = k
    return best


def worker() -> None:
    """One grouped pass (fresh process, the parent's backend pin)."""
    import jax
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.ops.adapt import AdaptStats

    inp, outp = os.environ["SCALE_IN"], os.environ["SCALE_OUT"]
    cycles = int(os.environ.get("SCALE_CYCLES", "6"))
    polish = os.environ.get("SCALE_POLISH", "0") == "1"
    vb = 3 if os.environ.get("SCALE_VERBOSE") else 0
    z, mesh, met, part = _load_state(inp)
    ngroups = int(part.max()) + 1
    stats = AdaptStats()
    t0 = time.perf_counter()
    # cap_mult stays at the API default: the prediction-weighted
    # partition (main) bounds every group's FINAL size by its weight
    # share, so the standard multiplier already covers the growth and
    # the group program keeps the proven-compilable shape.
    mesh2, met2, part_m = grouped_adapt_pass(
        mesh, met, ngroups, cycles=cycles, part=part, stats=stats,
        verbose=vb, polish=polish,
        cap_mult=float(os.environ.get("SCALE_CAPM", "3.0")))
    adapt_s = time.perf_counter() - t0
    # quiet-group scheduler instrumentation (parallel/sched.py): the
    # active-group trajectory, saved-dispatch counters and the chunk
    # pipeline's upload/compute/download/writeback split ride back to
    # the orchestrator so the SCALE artifact shows WHERE the grouped
    # wall time goes and what the scheduler saved
    sched_timers = {k: round(v, 3) for k, v in stats.sched_extra.items()
                    if k.endswith("_s")}
    _save_state(outp, mesh2, met2, part_m, extra={
        "adapt_s": adapt_s, "cycles_run": stats.cycles,
        "ops": np.asarray([stats.nsplit, stats.ncollapse, stats.nswap,
                           stats.nmoved], np.int64),
        "active_groups": np.asarray(
            stats.sched_extra.get("active_groups_per_block", []),
            np.int64),
        # chunk auto-tune (sched.recommend_group_chunk, logged by the
        # grouped pass): adopted only under PARMMG_GROUP_CHUNK=auto;
        # the overhead constant of its cost model is CALIBRATED from
        # this pass's measured pipeline segment timings (ROADMAP 1b)
        "chunk_recommendation": np.asarray(
            stats.sched_extra.get("chunk_recommendation", [0])[-1],
            np.int64),
        # NaN = this pass produced no calibration signal (unchunked or
        # empty segments) — distinct from a measured zero overhead
        "chunk_overhead": np.asarray(
            stats.sched_extra.get("chunk_overhead_units", [np.nan])[-1],
            np.float64),
        "group_dispatches": np.asarray(stats.group_dispatches, np.int64),
        "saved_dispatches": np.asarray(stats.group_dispatches_saved,
                                       np.int64),
        # group-slot executions the device-resident quiet mask
        # lax.cond-skipped (parallel/sched.py, PR 12)
        "cond_skipped": np.asarray(
            stats.sched_extra.get("cond_skipped_rows", 0), np.int64),
        "sched_timers": np.asarray(json.dumps(sched_timers)),
        "device": np.asarray(jax.default_backend()),
        # this worker's compile ledger rides back to the orchestrator
        # so the BENCH artifact shows per-pass compile churn
        "ledger": np.asarray(json.dumps(ledger_snapshot()))})


def main():
    # a parent that imports jax while its workers need the chip would
    # hold the chip against them: this layout runs on the CPU only
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        sys.exit("scale_big.py starts one worker process per pass and "
                 "runs on the CPU only: set JAX_PLATFORMS=cpu (the "
                 "one-process chip run is chip_smoke.py)")
    import jax
    import jax.numpy as jnp
    # NOTE: the orchestrator itself runs WITHOUT the persistent cache —
    # it is pinned to CPU and the XLA:CPU AOT cache is unreliable on
    # this image (tests/conftest.py rationale).  The module-level
    # set_cache_env() above only exports the env var so the TPU pass
    # workers inherit it.

    from parmmg_tpu.core.mesh import make_mesh, mesh_to_host
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.ops.quality import tet_quality
    from parmmg_tpu.parallel.groups import how_many_groups
    from parmmg_tpu.parallel.partition import (morton_partition,
                                               move_interfaces)
    from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric

    n = int(os.environ.get("SCALE_N", "56"))
    target = int(os.environ.get("SCALE_TARGET", "24576"))
    niter = max(1, int(os.environ.get("SCALE_NITER", "2")))

    tmp = os.environ.get("SCALE_TMP", "/tmp/parmmg_scale")
    os.makedirs(tmp, exist_ok=True)
    # --resume / SCALE_RESUME=1: restart from the newest COMPLETE pass
    # state (``.ok``-marked — see module docstring) instead of from
    # scratch; the skipped staging metadata rides in meta.json
    resume = "--resume" in sys.argv[1:] or \
        os.environ.get("SCALE_RESUME", "") == "1"
    it0 = 0
    phases = {}
    state = f"{tmp}/state0.npz"
    # run-identity knobs: stored in meta.json and required to match at
    # resume — a reused SCALE_TMP must never silently resume a run with
    # different SCALE_* knobs (a final-pass state in particular carries
    # an UN-displaced partition, so extending niter on it would break
    # the bit-identical contract)
    knobs = {"n": n, "target": target, "niter": niter,
             "cycles": int(os.environ.get("SCALE_CYCLES", "6"))}
    if resume:
        k = _find_resume(tmp, niter)
        meta_p = f"{tmp}/meta.json"
        if k is None or not os.path.exists(meta_p):
            print(f"scale: --resume requested but no complete state "
                  f"under {tmp}; starting fresh", file=sys.stderr)
            resume = False
        else:
            with open(meta_p) as f:
                meta = json.load(f)
            stored = {kk: meta.get(kk) for kk in knobs}
            if stored != knobs:
                print("scale: --resume refused: SCALE knobs differ "
                      f"from the checkpointed run ({stored} vs "
                      f"{knobs}); starting fresh", file=sys.stderr)
                resume = False
            elif k >= niter:
                # every pass already complete: the original run emitted
                # its artifact; re-emitting one with zero adapt seconds
                # would read as a throughput regression in the artifact
                # differ — nothing to resume, say so and stop
                print(f"scale: --resume: all {niter} passes already "
                      f"complete under {tmp}; nothing to resume",
                      file=sys.stderr)
                return
            else:
                it0 = k
                ntet0, ngroups = int(meta["ntet0"]), int(meta["ngroups"])
                state = f"{tmp}/state{k}.npz"
                print(f"scale: resuming from {state} "
                      f"(outer pass {k}/{niter})", file=sys.stderr)
    if not resume:
        # fresh start: drop stale pass states + markers so a LATER
        # resume can never mix runs
        import glob as _glob
        for f in _glob.glob(f"{tmp}/state*.npz*"):
            os.remove(f)
        it0 = 0
        t0 = time.perf_counter()
        vert, tet = cube_mesh(n)
        ntet0 = len(tet)
        phases["host_build"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        # host partition: morton only — fix_contiguity's python BFS is
        # an O(mesh) host stage this datapoint deliberately excludes
        # (group seams freeze identically either way).  The curve is
        # split by PREDICTED-final-density weights, not initial counts:
        # the shock slab grows ~6x while coarse regions shrink, so
        # equal-initial groups overflow their static caps exactly where
        # the work is (and a regrow means a fresh compile).  A tet of volume V in a region with target size h
        # ends as ~V/(h^3/(6 sqrt 2)) unit tets; the bisection
        # equilibrium overshoots the ideal count ~2.2x (measured, bench
        # fixture class).  weight = 1 + predicted bounds BOTH the
        # initial and the final group size by the group's weight share,
        # so one static cap fits all groups end to end.
        h = analytic_iso_metric(vert, "shock", h=1.5 / n)
        cent = vert[tet].mean(axis=1)
        p = vert[tet]
        vol = np.abs(np.einsum(
            "ij,ij->i", p[:, 1] - p[:, 0],
            np.cross(p[:, 2] - p[:, 0], p[:, 3] - p[:, 0]))) / 6.0
        h_tet = np.asarray(h)[tet].mean(axis=1)
        pred = 2.2 * vol / (0.1178 * np.maximum(h_tet, 1e-9) ** 3)
        w = 1.0 + pred
        ngroups = how_many_groups(int(w.sum()), int(1.5 * target))
        part = morton_partition(cent, ngroups, weights=w)
        phases["host_partition"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        mesh = make_mesh(vert, tet,
                         capP=2 * len(vert), capT=2 * len(tet))
        mesh = analyze_mesh(mesh).mesh
        met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
            jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)
        jax.block_until_ready(mesh.vert)
        phases["stage_analyze"] = time.perf_counter() - t0

        # ---- grouped passes, one fresh-client subprocess each ----------
        t0 = time.perf_counter()
        _save_state(state, mesh, met, part)
        _mark_ready(state)
        with open(f"{tmp}/meta.json", "w") as f:
            json.dump({"ntet0": int(ntet0), "ngroups": int(ngroups),
                       **knobs}, f)
        phases["state_io"] = time.perf_counter() - t0
        del mesh, met

    cycles_run = 0
    ops = np.zeros(4, np.int64)
    dev = "?"
    ledgers = {}
    active_traj = {}
    sched_timers = {}
    group_disp = 0
    saved_disp = 0
    cond_skipped = 0
    chunk_rec = 0
    chunk_overhead = {}
    for it in range(it0, niter):
        nxt = f"{tmp}/state{it + 1}.npz"
        env = dict(os.environ)
        env.update(SCALE_IN=state, SCALE_OUT=nxt, SCALE_WORKER="1",
                   SCALE_POLISH="1" if it == niter - 1 else "0")
        # chunked dispatch even on CPU workers (SCALE_GROUP_CHUNK,
        # default 8): chunking is what the quiet-group scheduler
        # compacts — on the chip it also bounds the per-dispatch HBM
        # (group_chunk docstring), on CPU the host staging is cheap and
        # skipping quiet groups is a straight win on this workload
        # (SCALE_r03: op counts collapse across cycles)
        env.setdefault("PARMMG_GROUP_CHUNK",
                       os.environ.get("SCALE_GROUP_CHUNK", "8"))
        t0 = time.perf_counter()
        # the pass is idempotent from its input state: on a worker
        # crash, retry in a fresh process through the shared
        # resilience wrapper — same PARMMG_RETRY_* knobs, backoff,
        # ladder events and counters as the in-process recovery paths
        from parmmg_tpu.resilience.recover import (RetryBudgetExhausted,
                                                   WorkerExitError,
                                                   retry_call)

        def _invoke_pass():
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env)
            if r.returncode != 0:
                raise WorkerExitError("scale.worker", r.returncode)
            return r

        try:
            retry_call(_invoke_pass, site="scale.worker")
        except RetryBudgetExhausted as e:
            raise RuntimeError(
                f"pass {it} worker failed after retries "
                f"({e.__cause__ or e})") from e
        phases[f"pass{it}_total"] = time.perf_counter() - t0
        z, mesh2, met2, part_m = _load_state(nxt)
        phases[f"pass{it}_adapt"] = float(z["adapt_s"])
        cycles_run += int(z["cycles_run"])
        ops += z["ops"]
        dev = str(z["device"])
        if "ledger" in z.files:
            ledgers[f"pass{it}"] = json.loads(str(z["ledger"]))
        if "active_groups" in z.files:
            active_traj[f"pass{it}"] = [int(v)
                                        for v in z["active_groups"]]
            group_disp += int(z["group_dispatches"])
            saved_disp += int(z["saved_dispatches"])
            sched_timers[f"pass{it}"] = json.loads(str(z["sched_timers"]))
        if "cond_skipped" in z.files:
            cond_skipped += int(z["cond_skipped"])
        if "chunk_overhead" in z.files and \
                np.isfinite(float(z["chunk_overhead"])):
            chunk_overhead[f"pass{it}"] = round(
                float(z["chunk_overhead"]), 4)
        if "chunk_recommendation" in z.files:
            chunk_rec = int(z["chunk_recommendation"])
            print(f"scale: pass {it} recommends PARMMG_GROUP_CHUNK="
                  f"{chunk_rec or 'unchunked'} (auto-tune; set "
                  "PARMMG_GROUP_CHUNK=auto to adopt)", file=sys.stderr)
        state = nxt
        if it + 1 < niter:
            t0 = time.perf_counter()
            _, tet_h, _, _, _ = mesh_to_host(mesh2)
            part2 = move_interfaces(tet_h, np.asarray(part_m),
                                    int(np.asarray(part_m).max()) + 1,
                                    nlayers=2)
            phases["ifc_displacement"] = \
                phases.get("ifc_displacement", 0.0) + \
                (time.perf_counter() - t0)
            # rewrite the state with the displaced partition, THEN mark
            # complete: a kill mid-rewrite resumes from the previous
            # marked state (re-running one pass, never corrupting one)
            _save_state(state, mesh2, met2, part2)
            _mark_ready(state)
        # the FINAL state is marked only after the artifact is emitted
        # (end of main): a kill during the post-adapt tail must leave
        # the last pass resumable, or the artifact could never be
        # produced without a full rerun

    # post-merge whole-mesh polish on the CPU backend: the grouped
    # polish cannot touch the FINAL seams (frozen in their own pass);
    # this full-width pass can (SCALE_MERGED_POLISH=0 skips it).
    from parmmg_tpu.ops.adapt import sliver_polish
    from parmmg_tpu.ops.repair import repair_mesh
    t0 = time.perf_counter()
    met2 = jnp.asarray(met2)
    mesh2 = jax.tree.map(jnp.asarray, mesh2)
    if os.environ.get("SCALE_MERGED_POLISH", "1") == "1":
        for w in range(3):
            mesh2, pc = sliver_polish(
                mesh2, met2, jnp.asarray(3000 + w, jnp.int32))
            pcn = np.asarray(pc)
            if int(pcn[0]) == 0 and int(pcn[1]) == 0:
                break
    phases["merged_polish"] = time.perf_counter() - t0

    # sequential tail repair (host, O(bad tets)) — the production
    # driver's _finish_run role
    t0 = time.perf_counter()
    mesh2, _nrep = repair_mesh(mesh2, met2)
    phases["repair_tail"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    tm = np.asarray(mesh2.tmask)
    q = np.asarray(tet_quality(mesh2, met2))[tm]
    phases["quality_pull"] = time.perf_counter() - t0

    # throughput accounting: live tets examined per
    # cycle / adapt wall seconds.  Worker numbers INCLUDE the one-time
    # compiles (reported separately in phases_s as passN_adapt vs
    # passN_total = adapt + state IO + process start).
    adapt_s = sum(v for k, v in phases.items() if k.endswith("_adapt"))
    examined = cycles_run * ntet0          # lower bound (mesh only grows)
    rate = examined / max(adapt_s, 1e-9) / 1e6
    # bench-side ledger regression check (compile governor teeth): any
    # entry point whose compiled-variant count grew since the newest
    # SCALE_r*.json artifact is flagged in the JSON and on stderr
    # (scripts/ledger_check.py --diff is the standalone comparison)
    ledger = {**ledgers, "host": ledger_snapshot()}
    regressions = _ledger_regressions_vs_previous(ledger)
    if regressions:
        print("scale: COMPILE-LEDGER VARIANT REGRESSIONS vs previous "
              "artifact:", file=sys.stderr)
        for r in regressions:
            print(f"scale:   {r}", file=sys.stderr)

    # canonical schema-versioned artifact (obs/artifact.py)
    from parmmg_tpu.obs.artifact import make_artifact
    print(json.dumps(make_artifact(
        "SCALE",
        metric="grouped_scale_throughput",
        value=round(rate, 4),
        unit="Mtets/sec/chip (incl. one-time compile)",
        extra={
            "niter": niter,
            **({"resumed_from_pass": it0} if it0 else {}),
            "ntets_initial": int(ntet0),
            "ntets_final": int(tm.sum()),
            "ngroups": int(ngroups),
            "cycles": int(cycles_run),
            "ops": [int(v) for v in ops],
            "qmin": round(float(q.min()), 4) if tm.any() else 0.0,
            "qmean": round(float(q.mean()), 4) if tm.any() else 0.0,
            "phases_s": {k: round(v, 2) for k, v in phases.items()},
            "device": dev,
            # quiet-group scheduler (parallel/sched.py): per-pass
            # active-group trajectory, total/saved chunk dispatches and
            # the pipeline's upload/compute/download/writeback split —
            # the win and the transfer/compute balance in one artifact
            "active_groups_per_block": active_traj,
            "group_dispatches": group_disp,
            "saved_dispatches": saved_disp,
            # device-resident quiet mask (PR 12): lax.cond-skipped
            # group-slot executions + the measured per-dispatch
            # overhead calibration feeding the chunk auto-tune
            "cond_skipped": cond_skipped,
            "chunk_recommendation": chunk_rec,
            "chunk_overhead_calibration": chunk_overhead,
            "sched_pipeline_s": sched_timers,
            # per-pass worker compile ledgers + the orchestrator's own
            # (compile governor): steady-state passes should show ~zero
            # fresh compiles once the persistent cache is warm
            "compile_ledger": ledger,
            "ledger_regressions": regressions,
        })))
    # only now is the run truly complete: mark the final state so a
    # later --resume knows there is nothing left to produce
    _mark_ready(state)


def _ledger_regressions_vs_previous(ledger: dict) -> list[str]:
    """Diff this run's (nested per-worker) ledger against the newest
    SCALE_r*.json in the repo root (shared logic:
    utils.compilecache.regressions_vs_latest_artifact)."""
    from parmmg_tpu.utils.compilecache import regressions_vs_latest_artifact
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    return regressions_vs_latest_artifact(root, "SCALE_r*.json", ledger)


if __name__ == "__main__":
    if os.environ.get("SCALE_WORKER") == "1":
        worker()
    else:
        main()
