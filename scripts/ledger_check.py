"""Compile-ledger budget gate (scripts/run_tests.sh --ledger).

Runs the steady-state migration scenario (4 outer iterations with
drifting interface sizes, CPU backend) — once at G=1 and once on the
grouped G=2 (groups x shards) layout — and FAILS (exit 1) when any
registered entry point exceeded its compiled-variant budget — the CI
teeth behind the compile governor (utils/compilecache): a change that
reintroduces per-iteration recompiles (exact static shapes, a fresh
jit object per call, an unbucketed budget) trips this gate without
anyone having to eyeball BENCH artifacts.

``--diff old.json new.json`` instead runs the cross-artifact regression
differ (obs/artifact.py ``artifact_diff``): both sides are upgraded to
the canonical schema, then compile-ledger variant growth (the historical
hard-fail class), headline-metric drops, qmin/qmean drops, scheduler
saved-dispatch shrinkage and disappearing metric counters are reported.
Exit 1 on ledger regressions; ``--strict`` also fails on metric/quality
regressions.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def diff_main(old_path: str, new_path: str, strict: bool = False) -> int:
    from parmmg_tpu.obs.artifact import artifact_diff
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    d = artifact_diff(old, new)
    for label, rows in (("LEDGER VARIANT REGRESSIONS", d["ledger"]),
                        ("METRIC REGRESSIONS", d["value"]),
                        ("QUALITY REGRESSIONS", d["quality"]),
                        ("notes", d["notes"])):
        if rows:
            print(f"{label}:", file=sys.stderr)
            for v in rows:
                print(f"  {v}", file=sys.stderr)
    bad = list(d["ledger"])
    if strict:
        bad += d["value"] + d["quality"]
    if bad:
        return 1
    print(f"artifact diff OK: no ledger"
          + ("" if not strict else "/metric/quality")
          + f" regressions ({old_path} -> {new_path})")
    return 0


if len(sys.argv) >= 2 and sys.argv[1] == "--diff":
    args = [a for a in sys.argv[2:] if a != "--strict"]
    if len(args) != 2:
        print("usage: ledger_check.py --diff [--strict] OLD.json "
              "NEW.json", file=sys.stderr)
        sys.exit(2)
    sys.exit(diff_main(args[0], args[1],
                       strict="--strict" in sys.argv[2:]))

os.environ["JAX_PLATFORMS"] = "cpu"
# the virtual multi-device CPU mesh (same setup as tests/conftest.py):
# the scenario shards over 2 devices
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# no persistent cache: a warm cache would hide fresh-variant compiles
from parmmg_tpu.utils.compilecache import disable_persistent_cache  # noqa: E402
disable_persistent_cache()

import numpy as np  # noqa: E402


def grouped_sched_gate() -> int:
    """Quiet-group scheduler compile-family gate: a chunked grouped
    pass with the scheduler ON must introduce ZERO new compile families
    versus the always-dispatch path — compaction gathers group slices
    for the SAME compiled [chunk, ...] program, so the later runs below
    (same process, jit caches warm from the scheduler-off run) may not
    compile anything new under any ``groups.*`` entry point.  The same
    contract covers the device-resident quiet mask (PARMMG_DEVICE_MASK,
    parallel/sched.py): the mask is ALWAYS an argument of the compiled
    block programs, so a mask-on run vs a mask-off run in one process
    must also add zero ``groups.*`` families — the ``lax.cond`` wrapper
    may not mint new variants."""
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.utils.compilecache import (ledger_violations,
                                               reset_ledger,
                                               variants_by_prefix)
    from parmmg_tpu.utils.fixtures import cube_mesh

    def run(sched: str, mask: str = "1"):
        os.environ["PARMMG_GROUP_SCHED"] = sched
        os.environ["PARMMG_DEVICE_MASK"] = mask
        vert, tet = cube_mesh(2)
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.full(m.capP, 0.35, m.vert.dtype)
        out, _, _ = grouped_adapt_pass(m, met, 3, cycles=2)
        assert int(np.asarray(out.tmask).sum()) > 0

    def grp_variants():
        return variants_by_prefix("groups.")

    # save/restore the operator's knob values
    prev = {k: os.environ.get(k)
            for k in ("PARMMG_GROUP_CHUNK", "PARMMG_GROUP_SCHED",
                      "PARMMG_DEVICE_MASK")}
    os.environ["PARMMG_GROUP_CHUNK"] = "1"
    try:
        reset_ledger()
        run("0", mask="0")            # legacy always-dispatch, no mask
        v0 = grp_variants()
        run("1", mask="0")            # compaction on, device mask off
        v1 = grp_variants()
        run("1", mask="1")            # compaction + device mask
        v2 = grp_variants()
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert v0.get("groups.adapt_block", 0) >= 1, \
        "grouped scenario no longer exercises groups.adapt_block"
    print("--- grouped quiet-scheduler scenario")
    if v1 != v0:
        print("SCHEDULER COMPILE-FAMILY REGRESSIONS (scheduler on "
              f"added variants): {v0} -> {v1}", file=sys.stderr)
        return 1
    if v2 != v1:
        print("DEVICE-MASK COMPILE-FAMILY REGRESSIONS (mask-on run "
              f"added variants vs mask-off): {v1} -> {v2}",
              file=sys.stderr)
        return 1
    bad = ledger_violations()
    if bad:
        print("\nLEDGER BUDGET VIOLATIONS (grouped scheduler):",
              file=sys.stderr)
        for v in bad:
            print(f"  {v}", file=sys.stderr)
        return 1
    print(f"grouped scheduler OK: zero new compile families ({v2}; "
          "scheduler AND device mask)")
    return 0


def hotloop_knob_gate() -> int:
    """Hot-loop knob compile-family gate (the cycle-cost demolition
    attacks, README "Hot-loop cycle costs"): flipping the facesort swap
    pairing, the donor-band collapse apply, the Pallas scoring prep or
    the Pallas sort engine may not mint a single new ``groups.*``
    compile family in a warm process.  The knobs are trace-time reads
    whose both settings produce bit-identical results, so the warm
    ``_GROUP_BLOCK_CACHE`` program from the first run legitimately
    serves the flipped runs (a stale entry is only a perf choice, never
    a correctness one)."""
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.utils.compilecache import (ledger_violations,
                                               reset_ledger,
                                               variants_by_prefix)
    from parmmg_tpu.utils.fixtures import cube_mesh

    KNOBS = ("PARMMG_SWAP_FACESORT", "PARMMG_COLLAPSE_BAND",
             "PARMMG_PALLAS_SCORE")

    def run(setting: str):
        for k in KNOBS:
            os.environ[k] = setting
        # cube(4): a capacity rung no earlier gate in this process has
        # compiled, so the knobs-off run below really compiles the
        # family (variants only count at compile time — a warm-cache
        # run would leave v0 empty and make the comparison vacuous)
        vert, tet = cube_mesh(4)
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.full(m.capP, 0.35, m.vert.dtype)
        out, _, _ = grouped_adapt_pass(m, met, 3, cycles=2)
        assert int(np.asarray(out.tmask).sum()) > 0

    prev = {k: os.environ.get(k)
            for k in KNOBS + ("PARMMG_GROUP_CHUNK",)}
    os.environ["PARMMG_GROUP_CHUNK"] = "1"
    try:
        reset_ledger()
        run("0")                      # all attacks off (legacy paths)
        v0 = variants_by_prefix("groups.")
        run("1")                      # all attacks on
        v1 = variants_by_prefix("groups.")
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert v0.get("groups.adapt_block", 0) >= 1, \
        "hot-loop knob scenario no longer exercises groups.adapt_block"
    print("--- hot-loop knob scenario "
          "(facesort/band/score/sort)")
    if v1 != v0:
        print("HOT-LOOP KNOB COMPILE-FAMILY REGRESSIONS (knobs-on run "
              f"added variants vs knobs-off): {v0} -> {v1}",
              file=sys.stderr)
        return 1
    bad = ledger_violations()
    if bad:
        print("\nLEDGER BUDGET VIOLATIONS (hot-loop knobs):",
              file=sys.stderr)
        for v in bad:
            print(f"  {v}", file=sys.stderr)
        return 1
    print(f"hot-loop knobs OK: zero new compile families ({v1}; "
          "facesort, collapse band, pallas score, pallas sort)")
    return 0


def serving_gate() -> int:
    """Serving compile-family gate: a warm pool serving tenants of two
    DIFFERENT bucket sizes must add ZERO ``groups.*`` compile-ledger
    families versus the batch grouped path run in the same process —
    the pool's slots are shape-identical to the standalone
    ``grouped_adapt_pass(ngroups=1)`` layout (same capacity-ladder
    rungs, same cached ``_group_block`` programs), so serving is
    compile-free after the per-bucket warmup any batch user pays.
    Doubles as a bit-for-bit parity check: each tenant's merged output
    must equal its standalone run (mesh fields + metric)."""
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import MESH_FIELDS, make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.serve.driver import ServeDriver
    from parmmg_tpu.utils.compilecache import (ledger_violations,
                                               reset_ledger,
                                               variants_by_prefix)
    from parmmg_tpu.utils.fixtures import cube_mesh

    cycles = 2

    def tenant(n, h):
        vert, tet = cube_mesh(n)
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.full(m.capP, h, m.vert.dtype)
        return m, met

    def grp_variants():
        return variants_by_prefix("groups.")

    reset_ledger()
    classes = ((2, 0.55), (3, 0.5))
    # batch warmup: the standalone grouped path per bucket size — this
    # is the only phase allowed to compile groups.* programs
    refs = {}
    for n, h in classes:
        m, met = tenant(n, h)
        out, met_m, _ = grouped_adapt_pass(m, met, 1, cycles=cycles)
        refs[n] = (out, met_m)
    v0 = grp_variants()
    assert v0.get("groups.adapt_block", 0) >= 1, \
        "serving warmup no longer exercises groups.adapt_block"
    drv = ServeDriver(slots_per_bucket=2, chunk=1, cycles=cycles)
    for n, h in classes:
        m, met = tenant(n, h)
        drv.submit(mesh=m, met=met, tenant=f"n{n}")
    rep = drv.run()
    v1 = grp_variants()
    print("--- serving scenario (2 tenants, 2 buckets, warm pool)")
    if rep["served"] != 2:
        print(f"SERVING GATE: expected 2 served tenants, got {rep}",
              file=sys.stderr)
        return 1
    if v1 != v0:
        print("SERVING COMPILE-FAMILY REGRESSIONS (warm pool added "
              f"variants): {v0} -> {v1}", file=sys.stderr)
        return 1
    for n, _h in classes:
        mesh, met_m = drv.fetch(f"n{n}")
        ref, kref = refs[n]
        for f in MESH_FIELDS:
            if not (np.asarray(getattr(mesh, f))
                    == np.asarray(getattr(ref, f))).all():
                print(f"SERVING PARITY: tenant n{n} field {f} differs "
                      "from the standalone grouped run", file=sys.stderr)
                return 1
        if not (np.asarray(met_m) == np.asarray(kref)).all():
            print(f"SERVING PARITY: tenant n{n} metric differs",
                  file=sys.stderr)
            return 1
    # daemon mode: the SAME tenants through the pool daemon's HTTP RPC
    # surface (in-process ephemeral-port daemon, so the compile ledger
    # is shared) must add ZERO groups.* families vs the in-process pool
    # above, and every fetched result must stay bit-identical to the
    # standalone run — the daemon is a transport, never a new program
    from parmmg_tpu.serve.client import ServeClient
    from parmmg_tpu.serve.daemon import PoolDaemon
    from parmmg_tpu.utils.fixtures import cube_mesh
    print("--- serving scenario (daemon mode, 2 tenants over HTTP)")
    daemon = PoolDaemon(port=0, slots_per_bucket=2, chunk=1,
                        cycles=cycles)
    daemon.start()
    try:
        cl = ServeClient(port=daemon.port)
        tids = {}
        for n, h in classes:
            vert, tet = cube_mesh(n)
            # full-capP metric: identical staging to tenant() above
            tids[n] = cl.submit(vert=vert, tet=tet,
                                met=np.full(4 * len(vert), h),
                                tenant=f"d{n}")
        for n, _h in classes:
            got = cl.wait(tids[n], timeout_s=600)
            if got["state"] != "done":
                print(f"SERVING GATE (daemon): tenant d{n} ended "
                      f"{got['state']}: {got.get('reason', '')}",
                      file=sys.stderr)
                return 1
            arrays = cl.fetch(tids[n])
            ref, kref = refs[n]
            for f in MESH_FIELDS:
                if not (arrays[f] == np.asarray(getattr(ref, f))).all():
                    print(f"SERVING PARITY (daemon): tenant d{n} field "
                          f"{f} differs from the standalone run",
                          file=sys.stderr)
                    return 1
            if not (arrays["met"] == np.asarray(kref)).all():
                print(f"SERVING PARITY (daemon): tenant d{n} metric "
                      "differs", file=sys.stderr)
                return 1
    finally:
        daemon.shutdown()
    v2 = grp_variants()
    if v2 != v1:
        print("SERVING COMPILE-FAMILY REGRESSIONS (daemon mode added "
              f"variants vs the in-process pool): {v1} -> {v2}",
              file=sys.stderr)
        return 1
    bad = ledger_violations()
    if bad:
        print("\nLEDGER BUDGET VIOLATIONS (serving):", file=sys.stderr)
        for v in bad:
            print(f"  {v}", file=sys.stderr)
        return 1
    print(f"serving OK: zero new compile families ({v2}), bit-for-bit "
          "parity with the batch grouped path (in-process AND daemon)")
    return 0


def main() -> int:
    from parmmg_tpu.utils.compilecache import (format_ledger,
                                               ledger_snapshot,
                                               ledger_violations,
                                               reset_ledger)
    from parmmg_tpu.utils.fixtures import steady_state_migration_scenario

    rc = 0
    # budgets are PER steady-state family: one compiled-shape family per
    # (fixture caps, G) — the ledger is reset between the two scenario
    # runs so the G=1 and grouped gates stay individually tight instead
    # of sharing a doubled allowance
    for label, kwargs, must_call in (
            ("G=1", dict(niter=4, cycles=2, n_shards=2),
             ("migrate_dev.device_migrate", "dist.interface_check")),
            ("G=2 grouped", dict(niter=3, cycles=2, n_shards=4,
                                 n_devices=2),
             ("dist.analysis_grouped", "dist.interface_check"))):
        reset_ledger()
        out = steady_state_migration_scenario(**kwargs)
        assert int(np.asarray(out.tmask).sum()) > 0
        led = ledger_snapshot()
        for entry in must_call:
            assert led.get(entry, {}).get("calls", 0) >= 1, \
                f"{label} scenario no longer exercises {entry}"
        print(f"--- {label} steady-state scenario")
        print(format_ledger())
        bad = ledger_violations()
        if bad:
            print(f"\nLEDGER BUDGET VIOLATIONS ({label}):",
                  file=sys.stderr)
            for v in bad:
                print(f"  {v}", file=sys.stderr)
            rc = 1
    # quiet-group scheduler gate: compaction must reuse the compiled
    # [chunk, ...] group program — zero new families with it enabled
    rc = max(rc, grouped_sched_gate())
    # hot-loop knob gate: facesort/band/score toggles add zero
    # groups.* families in a warm process (the warm-cache contract —
    # see hotloop_knob_gate)
    rc = max(rc, hotloop_knob_gate())
    # serving gate: a warm multi-tenant pool adds zero groups.*
    # families vs the batch grouped path (and matches it bit-for-bit)
    rc = max(rc, serving_gate())
    if rc == 0:
        print("\nledger OK: all entry points within variant budgets")
    return rc


if __name__ == "__main__":
    sys.exit(main())
