"""Compile governor regression tests (utils/compilecache).

The steady-state remesh/repartition loop re-runs the same programs
every iteration; the governor's job is that drifting per-iteration
sizes (interface widths, retag KF2/KN, comm-table pads) land on a
small fixed set of bucketed static shapes so the registered entry
points stop compiling fresh variants (ADVICE r3: retag_device compiled
nearly every iteration).  The ledger (jax.monitoring backend-compile
listener + registry decorator) is the measurement; these tests pin the
policy AND the end-to-end behavior on the CPU backend.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from parmmg_tpu.utils.compilecache import (
    bucket, governed, ledger_diff, ledger_snapshot, ledger_violations,
    reset_ledger)


def test_bucket_policy():
    # pow2: monotone, floored, >= n, few variants over a wide range
    assert bucket(1) == 256 and bucket(256) == 256 and bucket(257) == 512
    sizes = {bucket(n) for n in range(1, 4097)}
    assert sizes == {256, 512, 1024, 2048, 4096}
    for n in (1, 100, 1000, 4097):
        assert bucket(n) >= n
    # geo: bounded overshoot (<= 1.5x + 1), still O(log) variants
    for n in (70, 500, 3000, 40000):
        b = bucket(n, floor=64, scheme="geo")
        assert n <= b <= int(1.5 * n) + 2
    assert len({bucket(n, floor=64, scheme="geo")
                for n in range(1, 5000)}) <= 12
    # cap clamps (caller must handle a capped bucket < n)
    assert bucket(5000, floor=1024, cap=3000) == 3000
    import pytest
    with pytest.raises(ValueError):
        bucket(10, scheme="fib")


def test_ledger_attribution_and_budget():
    import jax
    reset_ledger()

    @governed("test.toy", budget=1)
    @jax.jit
    def toy(x):
        return x * 2 + 1

    toy(jnp.ones(8))
    toy(jnp.ones(8))          # cache hit: no new compile
    rec = ledger_snapshot()["test.toy"]
    assert rec["calls"] == 2
    assert rec["variants"] == 1 and rec["compiles"] >= 1
    assert not any(v.startswith("test.toy") for v in ledger_violations())
    toy(jnp.ones(16))         # second shape: budget 1 exceeded
    assert ledger_snapshot()["test.toy"]["variants"] == 2
    assert any(v.startswith("test.toy") for v in ledger_violations())


def test_session_id_guard_and_multiway_run_guard():
    """Satellite guards (ADVICE r3): int32 session-id overflow check and
    the non-manifold (3+ shard) exposed-face run detector."""
    from parmmg_tpu.parallel.migrate_dev import (has_multiway_face_run,
                                                 session_ids_fit)
    assert session_ids_fit(0, 8, 4096)
    assert session_ids_fit(2 ** 31 - 8 * 4096 - 1, 8, 4096)
    assert not session_ids_fit(2 ** 31 - 8 * 4096, 8, 4096)
    assert not session_ids_fit(2 ** 31, 2, 256)
    # eq = consecutive-equality mask of lexsorted face keys
    assert not has_multiway_face_run(np.array([], bool))
    assert not has_multiway_face_run(np.array([True], bool))
    assert not has_multiway_face_run(
        np.array([True, False, True, False], bool))     # pairs only
    assert has_multiway_face_run(
        np.array([False, True, True, False], bool))     # a 3-run
    assert has_multiway_face_run(np.array([True] * 3, bool))  # a 4-run


def test_ledger_diff_flags_variant_growth():
    """The regression comparison (ledger_check.py --diff, scale_big.py
    vs the previous SCALE artifact): growth on a shared entry
    is flagged, new entries and equal counts are not, and the nested
    per-worker shape scale_big emits is flattened per worker."""
    old = {"a": {"variants": 1}, "b": {"variants": 2}}
    new = {"a": {"variants": 3}, "b": {"variants": 2},
           "c": {"variants": 9}}
    bad = ledger_diff(old, new)
    assert bad == ["a: 1 -> 3 compiled variants"]
    assert ledger_diff(new, new) == []
    nested_o = {"pass0": {"x": {"variants": 1}}, "host": {"x":
                                                          {"variants": 1}}}
    nested_n = {"pass0": {"x": {"variants": 2}}, "host": {"x":
                                                          {"variants": 1}}}
    assert ledger_diff(nested_o, nested_n) == \
        ["pass0/x: 1 -> 2 compiled variants"]


@pytest.fixture(scope="module")
def scenario():
    """ONE run of the shared steady-state scenario
    (utils/fixtures.steady_state_migration_scenario) feeding every test
    in this module: the ledger-budget gate AND the burned-down
    migration gates from test_migrate ride the same compiled variants,
    so tier-1 pays the SPMD compile once (the slow-tier burn-down
    contract).  merge_shards calls are counted across the run for the
    no-intermediate-merge gate."""
    from parmmg_tpu.parallel import distribute
    from parmmg_tpu.utils.fixtures import steady_state_migration_scenario

    calls = {"n": 0}
    orig = distribute.merge_shards

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    distribute.merge_shards = counting
    try:
        reset_ledger()
        out, met, part = steady_state_migration_scenario(
            niter=4, cycles=2, n_shards=2, return_all=True)
    finally:
        distribute.merge_shards = orig
    return out, met, part, calls["n"], ledger_snapshot()


def test_migration_steady_state_compiles_bounded(scenario):
    """4 migration iterations with drifting interface sizes: the retag
    and halo entry points must stay within <= 2 compiled variants (the
    bucketed shapes absorb the drift) instead of ~1 fresh compile per
    iteration."""
    out, _met, _part, _nmerge, led = scenario
    assert int(np.asarray(out.tmask).sum()) > 0

    # the scenario must actually exercise the steady-state loop
    assert led["migrate_dev.device_migrate"]["calls"] >= 3
    assert led["migrate_dev.retag_device"]["calls"] >= 1
    for entry, lim in (("migrate_dev.retag_device", 2),
                       ("migrate_dev.extend_ids_device", 2),
                       ("migrate.flood_labels", 2),
                       ("dist.interface_check", 2)):
        rec = led[entry]
        assert rec["variants"] <= lim, \
            f"{entry}: {rec['variants']} compiled variants (> {lim}) — " \
            "steady-state recompile churn regressed"
    assert ledger_violations() == []


def test_multi_iteration_no_intermediate_merge(scenario):
    """Burned down from test_migrate (slow tier): >= 2 outer iterations
    with NO full-mesh merge except the final output merge (VERDICT r1
    #5), asserted on the shared scenario run — plus the adjacency
    symmetry, manifold, volume and quality-floor gates the original
    carried.  The shrunk fixture is 2-shard; the K>1-neighbor ifc-mode
    loop keeps its coverage in the slow tier
    (test_grouped_analysis.test_grouped_refresh_taken_on_g2_driver_run
    runs 4 logical shards through the same driver)."""
    out, met, _part, nmerge, _led = scenario
    assert nmerge == 1, "outer iterations must not merge the world"
    from parmmg_tpu.core.mesh import mesh_to_host
    from parmmg_tpu.ops.adjacency import build_adjacency, check_adjacency
    from parmmg_tpu.ops.quality import tet_quality
    vert_h, tet_h, _, _, _ = mesh_to_host(out)
    p = vert_h[tet_h]
    vol = np.einsum("ij,ij->i", p[:, 1] - p[:, 0],
                    np.cross(p[:, 2] - p[:, 0], p[:, 3] - p[:, 0])) / 6.0
    assert (vol > 0).all(), "inverted tets after the final merge"
    assert np.isclose(vol.sum(), 1.0, rtol=1e-4)
    faces = np.sort(np.stack([
        tet_h[:, [1, 2, 3]], tet_h[:, [0, 2, 3]],
        tet_h[:, [0, 1, 3]], tet_h[:, [0, 1, 2]]], axis=1
    ).reshape(-1, 3), axis=1)
    _, cnt = np.unique(faces, axis=0, return_counts=True)
    assert cnt.max() <= 2, "non-manifold face after migration + merge"
    out2 = build_adjacency(out)
    assert check_adjacency(out2) == {"asymmetric": 0, "face_mismatch": 0}
    q = np.asarray(tet_quality(out2, met))[np.asarray(out2.tmask)]
    assert q.min() > 0.02


def test_migration_moves_interface_band(scenario):
    """Burned down from test_migrate (slow tier): after the migration
    iterations the displaced partition labels are a valid source-shard
    assignment of every live tet (the comm echo inside the loop raises
    on an ordering violation, so reaching here also proves it held)."""
    out, _met, part, _nmerge, _led = scenario
    assert part.min() >= 0 and part.max() < 2
    assert len(part) == int(np.asarray(out.tmask).sum())
