"""Benchmark: Mtets remeshed/sec/chip on the real device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Workload: structured cube with a planar-shock isotropic size map (the
aniso-torus CI analogue of the reference matrix,
cmake/testing/pmmg_tests.cmake:25-38), adapted by repeated jitted cycles
(split/collapse/swap/smooth waves).  Throughput = live tets examined per
wall-second, after one warm-up cycle (compile excluded).

``vs_baseline``: the reference publishes no numbers (BASELINE.md), and a
measured in-image baseline is IMPOSSIBLE: ParMmg hard-requires MPI and
METIS and builds Mmg via cmake download — none of mpicc/mpi.h/metis.h
exist in this image and egress is zero (verified 2026-07-30; see
BASELINE.md "calibration basis").  The 0.4 Mtets/s figure is therefore a
documented calibration, not a guess: sequential Mmg3d-class remeshers
process ~40-60k tets/s/core for quality-driven isotropic adaptation on
~3 GHz x86 (the rate class reported across the Mmg/tet-remeshing
literature and consistent with Mmg CI runtimes), and the ParMmg
companion paper (Cirrottola & Froehly, inria hal-02386837 — cited from
README.md:97-99) reports near-linear strong scaling at 8 ranks for the
remesh phase; 8 ranks x 50k tets/s x ~0.85-0.9 efficiency ~= 0.34-0.45
-> 0.4 chosen as the round midpoint, deliberately on the high side so
``vs_baseline`` never flatters us.  North star (BASELINE.json): >=5x
that at equal min quality.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# calibrated 8-rank CPU ParMmg estimate — see module docstring + BASELINE.md
BASELINE_MTETS_PER_SEC = 0.4


def main() -> None:
    # persistent compile cache: the adapt-cycle graph takes minutes to
    # compile cold; cached executables make repeated bench runs start
    # fast (utils/compilecache, the rule the CLI uses).  No chip, no
    # number: without the caller's JAX_PLATFORMS=cpu pin anything but
    # a TPU is an error.
    from parmmg_tpu.utils.compilecache import (backend_or_fail,
                                               enable_persistent_cache,
                                               ledger_snapshot)
    import jax
    import jax.numpy as jnp
    device = backend_or_fail()
    if device["platform"] != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "") != "cpu":
        sys.exit(f"bench: device is {device['platform']}, not tpu")
    enable_persistent_cache()

    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.active import adapt_cycles_auto
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.ops.quality import tet_quality
    from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric

    n = int(os.environ.get("BENCH_N", "16"))          # 6*n^3 tets
    cycles = int(os.environ.get("BENCH_CYCLES", "9"))
    block = int(os.environ.get("BENCH_BLOCK", "9"))   # fused cycles/dispatch
    bdiv = int(os.environ.get("BENCH_BUDGET_DIV", "8"))  # wave top-K div
    cap = int(os.environ.get("BENCH_CAP", "8"))       # capacity factor

    vert, tet = cube_mesh(n)
    # capacity: midpoint bisection against LLONG=sqrt(2)/LSHRT=1/sqrt(2)
    # equilibrates with edges at ~0.7-1.0 of target, i.e. ~2-2.5x the
    # ideal-tet count — ~6.3x the initial tets on this fixture.  A
    # capacity-saturated mesh capacity-drops residual split winners
    # every cycle (overflow flag permanently set), which both truncates
    # the workload and vetoes the worklist fast path
    mesh = make_mesh(vert, tet, capP=cap * len(vert), capT=cap * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.5 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)

    # block schedule: global cycle indices keep the swap cadence identical
    # to the unfused host driver (swap every 3rd global cycle)
    warm_cycles = 2 * block
    sched = []
    b = 0
    while b < cycles:
        nc = min(block, cycles - b)
        sched.append((b, nc, (warm_cycles + b) % 3))
        b += nc

    # warm-up: TWO blocks.  The first compiles for the host-staged input
    # layout; its outputs are device arrays with a different layout, so
    # the very next call triggers a SECOND compile — running it here (not
    # in the timed loop) is what kills the consistent ~170s first-block
    # artifact.  Then warm every other distinct flavor by EXECUTING it on
    # a copy of the state (AOT .lower().compile() would not populate the
    # jit dispatch cache).  The auto block (ops/active.py) carries the
    # worklist state (dirty, okflag); each cycle inside runs
    # active-scoped when the worklist is valid and fits — the same
    # program the production driver dispatches.
    def _flags(nc, off):
        return tuple((c + off) % 3 == 2 for c in range(nc))

    dirty = jnp.zeros(mesh.capP, bool)
    okflag = jnp.asarray(False)
    m1, k1, dirty, okflag, wcnt = adapt_cycles_auto(
        mesh, met, dirty, okflag, jnp.asarray(0, jnp.int32),
        swap_flags=_flags(block, 0), budget_div=bdiv)
    jax.block_until_ready(wcnt)
    m1, k1, dirty, okflag, wcnt = adapt_cycles_auto(
        m1, k1, dirty, okflag, jnp.asarray(block, jnp.int32),
        swap_flags=_flags(block, block % 3), budget_div=bdiv)
    jax.block_until_ready(wcnt)
    for nc, off in sorted({(nc, off) for _, nc, off in sched}
                          - {(block, 0)}):
        mc = jax.tree.map(jnp.copy, m1)
        kc = jnp.copy(k1)
        dc = jnp.copy(dirty)
        _, _, _, _, c = adapt_cycles_auto(
            mc, kc, dc, okflag, jnp.asarray(0, jnp.int32),
            swap_flags=_flags(nc, off), budget_div=bdiv)
        jax.block_until_ready(c)

    # timed loop: cycles run in fused blocks of `block` (one dispatch +
    # ONE counter pull per block).
    ntet0 = int(np.asarray(wcnt)[-1][5])          # live tets after warm-up
    m, k = m1, k1
    live, times = [], []
    prev_live = ntet0
    narrow_cycles = 0
    for b, nc, off in sched:
        t0 = time.perf_counter()
        m, k, dirty, okflag, counts = adapt_cycles_auto(
            m, k, dirty, okflag,
            jnp.asarray(warm_cycles + b, jnp.int32),
            swap_flags=_flags(nc, off), budget_div=bdiv)
        cs = np.asarray(counts)                   # blocks on this block
        times.append(time.perf_counter() - t0)
        narrow_cycles += int(cs[:, 7].sum())
        if os.environ.get("BENCH_DEBUG", "") == "1":
            for r in cs:
                nact = int(r[8]) if len(r) > 8 else -1
                oki = int(r[9]) if len(r) > 9 else -1
                print(f"bench:   cycle counts split={int(r[0]):6d} "
                      f"col={int(r[1]):6d} swap={int(r[2]):6d} "
                      f"move={int(r[3]):6d} ovf={int(r[4])} "
                      f"live={int(r[5]):6d} "
                      f"defer={int(r[6])} narrow={int(r[7])} "
                      f"nact={nact} ok={oki}", file=sys.stderr)
        # tets examined this block = sum over cycles of live-at-entry
        entries = [prev_live] + [int(r[5]) for r in cs[:-1]]
        live.append(int(np.sum(entries)))
        prev_live = int(cs[-1][5])
    # A one-chip machine shares its host's CPU cores, so a block can
    # stall on the host.  Steady-state throughput is therefore the
    # MEDIAN per-block rate — robust to a stalled block without the
    # upward bias of a max; the sum-based rate is reported alongside for
    # transparency.
    rates = [lv / t for lv, t in zip(live, times)]
    mtets_per_sec = float(np.median(rates)) / 1e6
    mtets_sum = float(np.sum(live)) / float(np.sum(times)) / 1e6
    if min(times) * 3 < max(times):
        print(f"bench: block times {['%.2f' % t for t in times]}s spread "
              ">3x; reporting median block rate",
              file=sys.stderr)

    # bad-element polish + sequential tail repair before the quality
    # report — the SAME untimed quality tail the production driver runs
    # after the sizing loop (adapt_mesh polish + driver._finish_run
    # repair); throughput is measured on the steady-state sizing cycles
    # only, quality is reported for the full pipeline's output
    from parmmg_tpu.ops.adapt import sliver_polish
    from parmmg_tpu.ops.repair import repair_mesh

    def _quality_tail(mm, kk, wave0, use_met=False):
        for w in range(6):
            mm, pc = sliver_polish(mm, kk,
                                   jnp.asarray(wave0 + w, jnp.int32))
            pcn = np.asarray(pc)
            if int(pcn[0]) == 0 and int(pcn[1]) == 0:
                break
        mm, _ = repair_mesh(mm, kk)
        # iso reports Euclidean quality (the rounds-1..3 protocol, the
        # MMG5_caltet_iso convention); ANISO reports METRIC quality —
        # in an anisotropic metric the flattened elements are the
        # target shape and their Euclidean quality is meaningless
        qq = np.asarray(tet_quality(mm, kk) if use_met
                        else tet_quality(mm))
        tmm = np.asarray(mm.tmask)
        return (mm, int(tmm.sum()),
                float(qq[tmm].min()) if tmm.any() else 0.0,
                float(qq[tmm].mean()) if tmm.any() else 0.0)

    m, ntets_final, qmin, qmean = _quality_tail(m, k, 100)

    # ---- aniso datapoint (reference CI's torus-aniso analogue) ----------
    # a smaller planar-shock TENSOR-metric workload, same protocol in
    # miniature: warm one block, time the next ones.  Off by default
    # only via BENCH_ANISO=0.
    aniso = None
    if os.environ.get("BENCH_ANISO", "1") == "1":
        from parmmg_tpu.utils.fixtures import analytic_ani_metric
        n_a = int(os.environ.get("BENCH_ANISO_N", "12"))
        vert_a, tet_a = cube_mesh(n_a)
        mesh_a = make_mesh(vert_a, tet_a, capP=3 * len(vert_a),
                           capT=3 * len(tet_a))
        mesh_a = analyze_mesh(mesh_a).mesh
        ha = analytic_ani_metric(vert_a, "shock", h=1.5 / n_a)
        met_a = jnp.zeros((mesh_a.capP, 6), mesh_a.vert.dtype)
        met_a = met_a.at[: len(ha)].set(jnp.asarray(ha))
        met_a = met_a.at[len(ha):, 0].set(1.0).at[len(ha):, 3].set(
            1.0).at[len(ha):, 5].set(1.0)
        da = jnp.zeros(mesh_a.capP, bool)
        oka = jnp.asarray(False)
        ma, ka_ = mesh_a, met_a
        ma, ka_, da, oka, ca = adapt_cycles_auto(
            ma, ka_, da, oka, jnp.asarray(0, jnp.int32),
            swap_flags=_flags(block, 0), budget_div=bdiv)
        jax.block_until_ready(ca)
        prev_a = int(np.asarray(ca)[-1][5])
        lv_a, tm_a = 0, 0.0
        for b in range(2):
            t0 = time.perf_counter()
            ma, ka_, da, oka, ca = adapt_cycles_auto(
                ma, ka_, da, oka,
                jnp.asarray(block * (1 + b), jnp.int32),
                swap_flags=_flags(block, (block * (1 + b)) % 3),
                budget_div=bdiv)
            cs_a = np.asarray(ca)
            tm_a += time.perf_counter() - t0
            lv_a += prev_a + int(np.sum(cs_a[:-1, 5]))
            prev_a = int(cs_a[-1, 5])
        ma, nta, qmin_a, qmean_a = _quality_tail(ma, ka_, 200,
                                                 use_met=True)
        aniso = {"mtets_per_sec": round(lv_a / tm_a / 1e6, 4),
                 "ntets_final": nta,
                 "qmin": round(qmin_a, 4),
                 "qmean": round(qmean_a, 4)}

    # ---- grouped-analysis extraction probe (ROADMAP 4a, closed) ---------
    # dist_analysis_grouped now extracts the [12*capT] record table ONCE
    # per group per refresh (the PR-12 fusion: phase 1 carries the
    # verdict bits across the map, the tail re-derives only cheap
    # endpoint gathers).  extract1x_s = measured seconds of ONE
    # extraction at the bench mesh's shape — i.e. the per-group
    # per-refresh cost the fusion REMOVED (before = 2x this per group,
    # after = 1x).  Replaces the retired extract2x_s decision input.
    extract1x_s = None
    if os.environ.get("BENCH_EXTRACT2X", "1") == "1":   # knob name kept
        try:
            from parmmg_tpu.parallel.analysis_dev import \
                extract_probe_seconds
            glo_p = jnp.arange(m.vert.shape[0], dtype=jnp.int32)
            extract1x_s = round(extract_probe_seconds(m, glo_p), 5)
        except Exception as e:          # probe must never kill the bench
            print(f"bench: extract1x probe failed ({e!r})",
                  file=sys.stderr)

    # ---- quiet-group scheduler datapoint (opt-in: BENCH_GROUPED=1) ------
    # the device-resident quiet-mask before/after (PR 12): the SAME
    # grouped shock pass runs UNCHUNKED twice in one process — mask off
    # (every lax.map slot computes, the pre-PR-12 steady state: at
    # chunk 0 host compaction cannot skip anything) then mask on
    # (lax.cond identity for quiet slots) — through the same compiled
    # program, and the artifact records both steady-state seconds/cycle
    # plus a byte-compare of the merged outputs (extra.parity_ok).
    # Opt-in because the group block is a fresh compile family on a
    # cold cache; scripts/scale_big.py carries the same counters on the
    # real grouped workload.
    group_sched = None
    parity_ok = None
    incr_topo = None
    if os.environ.get("BENCH_GROUPED", "0") == "1":
        from parmmg_tpu.core.mesh import MESH_FIELDS
        from parmmg_tpu.ops.adapt import AdaptStats
        from parmmg_tpu.parallel.groups import grouped_adapt_pass
        n_g = int(os.environ.get("BENCH_GROUPED_N", "6"))
        ngr = 3
        cycles_g = int(os.environ.get("BENCH_GROUPED_CYCLES", "12"))
        prev_env = {k: os.environ.get(k)
                    for k in ("PARMMG_GROUP_CHUNK", "PARMMG_DEVICE_MASK",
                              "PARMMG_INCR_TOPO")}
        os.environ["PARMMG_GROUP_CHUNK"] = "0"
        # x-slab groups on the shock metric, with the far field CLAMPED
        # into the metric dead band (h <= 1.3/n: edges stay inside
        # (LSHRT, LLONG), no far-field coarsening) — the CFD-style
        # shock-capture scenario: refine the front into an
        # already-adequate background mesh.  The refinement band
        # (x=0.5) lives in the middle slab, so the outer slabs hit
        # their fixed point within the first swap-inclusive block —
        # the quiet-group population whose wave math the device mask
        # elides.  (The unclamped bench metric coarsens the far field
        # ~2-3x, a collapse trickle that keeps every group active to
        # the last cycle; a morton split additionally puts the shock
        # in every group — neither layout ever shows the steady state
        # the scheduler exists for.)
        vg, tg = cube_mesh(n_g)
        cent_g = vg[tg].mean(axis=1)
        part_g = np.minimum((cent_g[:, 0] * ngr).astype(np.int64),
                            ngr - 1)

        def run_grouped(mask: str, reps: int = 1):
            # the pass is deterministic from its input: repeat runs
            # produce identical bytes, so min-of-reps is a pure timing
            # de-noiser (the 1-core host shows ~10% run-to-run spread)
            os.environ["PARMMG_DEVICE_MASK"] = mask
            best = None
            for _ in range(max(1, reps)):
                mg = make_mesh(vg, tg, capP=4 * len(vg),
                               capT=4 * len(tg))
                mg = analyze_mesh(mg).mesh
                hg = np.minimum(
                    analytic_iso_metric(vg, "shock", h=1.5 / n_g),
                    1.3 / n_g)
                kg = jnp.zeros(mg.capP, mg.vert.dtype).at[
                    : len(hg)].set(jnp.asarray(hg, mg.vert.dtype)).at[
                    len(hg):].set(1.0)
                st_g = AdaptStats()
                t0 = time.perf_counter()
                out_g, met_g, _ = grouped_adapt_pass(mg, kg, ngr,
                                                     cycles=cycles_g,
                                                     part=part_g,
                                                     stats=st_g)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            return out_g, met_g, st_g, best
        try:
            run_grouped("0")                      # compile warm-up
            ref_g, kref_g, st0, t_off = run_grouped("0", reps=3)
            chk_g, kchk_g, st1, t_on = run_grouped("1", reps=3)
            parity_ok = bool(
                all((np.asarray(getattr(ref_g, f))
                     == np.asarray(getattr(chk_g, f))).all()
                    for f in MESH_FIELDS)
                and (np.asarray(kref_g) == np.asarray(kchk_g)).all())
            # one CHUNKED mask-on run: the double-buffered pipeline's
            # measured segment timings feed the chunk auto-tune's
            # overhead calibration (sched.calibrate_dispatch_overhead,
            # ROADMAP 1b) — recorded so the artifact carries a real
            # calibrated value, not just the wiring
            os.environ["PARMMG_GROUP_CHUNK"] = "2"
            _, _, st2, _ = run_grouped("1")
            os.environ["PARMMG_GROUP_CHUNK"] = "0"
            # incremental-topology A/B (PARMMG_INCR_TOPO, ops/topo_incr):
            # the SAME mask-on pass re-runs with the knob on — a traced
            # scalar, so it rides the compiled programs already warmed
            # above (ledger_check.py --diff shows zero groups.* growth).
            # The knob-off arm IS the mask-on run (t_on); outputs AND op
            # counters must be bit-identical (exactness by construction:
            # the dirty band re-keys exactly the slots whose keys could
            # have changed, overflow falls back to the full rebuild)
            os.environ["PARMMG_INCR_TOPO"] = "1"
            inc_g, kinc_g, st3, t_inc = run_grouped("1", reps=3)
            os.environ.pop("PARMMG_INCR_TOPO", None)
            incr_parity = bool(
                all((np.asarray(getattr(chk_g, f))
                     == np.asarray(getattr(inc_g, f))).all()
                    for f in MESH_FIELDS)
                and (np.asarray(kchk_g) == np.asarray(kinc_g)).all()
                and (st3.nsplit, st3.ncollapse, st3.nswap, st3.nmoved)
                == (st1.nsplit, st1.ncollapse, st1.nswap, st1.nmoved))
            incr_topo = {
                "off_s_per_cycle": round(t_on / max(st1.cycles, 1), 4),
                "on_s_per_cycle": round(t_inc / max(st3.cycles, 1), 4),
                "speedup": round(t_on / t_inc, 3),
                "parity_ok": incr_parity,
                # per-cycle dirty-tet counts (band occupancy the merge
                # absorbed; > band width = full-rebuild fallback cycles)
                "dirty_per_cycle":
                    st3.sched_extra.get("incr_dirty_per_cycle", []),
            }
            group_sched = {
                "ngroups": ngr,
                "cycles": st1.cycles,
                "mask_off_adapt_s": round(t_off, 3),
                "mask_on_adapt_s": round(t_on, 3),
                "mask_off_s_per_cycle":
                    round(t_off / max(st0.cycles, 1), 4),
                "mask_on_s_per_cycle":
                    round(t_on / max(st1.cycles, 1), 4),
                "cond_skipped_rows":
                    st1.sched_extra.get("cond_skipped_rows", 0),
                "dispatches": st1.group_dispatches,
                "saved_dispatches": st1.group_dispatches_saved,
                "active_groups_per_block":
                    st1.sched_extra.get("active_groups_per_block", []),
                # measured on the chunked (chunk=2) pipeline run
                "chunk_overhead_units":
                    st2.sched_extra.get("chunk_overhead_units", []),
                "chunked_saved_dispatches": st2.group_dispatches_saved,
                "chunked_cond_skipped":
                    st2.sched_extra.get("cond_skipped_rows", 0),
                "parity_ok": parity_ok,
            }
        finally:
            for k, v in prev_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # a phase-timing capture handed in by the caller (the script that
    # wrote one, scripts/profile_adapt.py, is gone: PR 28)
    profile_phases = None
    pp = os.environ.get("BENCH_PROFILE_JSON", "")
    if pp and os.path.exists(pp):
        with open(pp) as f:
            profile_phases = json.load(f)

    # ledger regression check against the previous round's artifact:
    # any entry point whose compiled-variant count GREW since the last
    # BENCH_r*.json is flagged in the JSON and on stderr (the bench-side
    # teeth of the compile governor; scripts/ledger_check.py --diff is
    # the standalone form of the same comparison)
    ledger = ledger_snapshot()
    regressions = _ledger_regressions_vs_previous(ledger)
    if regressions:
        print("bench: COMPILE-LEDGER VARIANT REGRESSIONS vs previous "
              "artifact:", file=sys.stderr)
        for r in regressions:
            print(f"bench:   {r}", file=sys.stderr)

    # canonical schema-versioned artifact (obs/artifact.py): the legacy
    # top-level keys stay put, the env/metrics/trace blocks ride along
    from parmmg_tpu.obs.artifact import make_artifact
    print(json.dumps(make_artifact(
        "BENCH",
        metric="adapt_cycle_throughput",
        value=round(mtets_per_sec, 4),
        unit="Mtets/sec/chip",
        vs_baseline=round(mtets_per_sec / BASELINE_MTETS_PER_SEC, 3),
        extra={"ntets_final": ntets_final, "qmin": round(qmin, 4),
               "qmean": round(qmean, 4), "cycles": cycles,
               "sum_rate": round(mtets_sum, 4),
               "narrow_cycles": narrow_cycles,
               "aniso": aniso,
               # single [12*capT] extraction cost (= the per-group
               # per-refresh saving of the PR-12 grouped-analysis
               # fusion; replaces the retired extract2x_s) + the
               # device-mask before/after datapoint (BENCH_GROUPED=1)
               "extract1x_s": extract1x_s,
               "group_sched": group_sched,
               "parity_ok": parity_ok,
               # incremental-topology A/B (BENCH_GROUPED=1): same-machine
               # s/cycle with PARMMG_INCR_TOPO off vs on + dirty-band
               # trajectory; outputs bit-identical (parity_ok)
               "incr_topo": incr_topo,
               "profile_phases": profile_phases,
               # where the numbers were taken: platform, device_kind
               # and device count as jax reports them
               "device": device,
               # compile-churn accounting (utils/compilecache): per
               # governed entry point {calls, variants, compiles,
               # compile_s} — a regression shows up as variants or
               # compiles growing with the cycle count
               "compile_ledger": ledger,
               "ledger_regressions": regressions,
               # free-form round context (BENCH_NOTES env) — e.g. a
               # runner-image change that shifts absolute times, with
               # the same-machine seed re-measurement for comparison
               "notes": os.environ.get("BENCH_NOTES") or None})))


def _ledger_regressions_vs_previous(ledger: dict) -> list[str]:
    """Compare this run's compile ledger against the NEWEST BENCH_r*.json
    next to this script (shared logic:
    utils.compilecache.regressions_vs_latest_artifact)."""
    from parmmg_tpu.utils.compilecache import regressions_vs_latest_artifact
    here = os.path.dirname(os.path.abspath(__file__))
    return regressions_vs_latest_artifact(here, "BENCH_r*.json", ledger)


if __name__ == "__main__":
    main()
