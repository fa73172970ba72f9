"""Two-level group decomposition tests (parallel/groups.py).

Reference semantics: ``-mesh-size`` bounds the per-group element count
(howManyGroups, grpsplit_pmmg.c:47,1589-1614); groups are remeshed with
their seams frozen, seams are displaced between iterations.  Gates are
quality/conformity, not exit codes.
"""
import numpy as np
import jax.numpy as jnp

from parmmg_tpu.core.mesh import make_mesh, tet_volumes
from parmmg_tpu.core import constants as C
from parmmg_tpu.ops.adjacency import build_adjacency, check_adjacency
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.quality import tet_quality
from parmmg_tpu.parallel.groups import how_many_groups, grouped_adapt
from parmmg_tpu.utils.fixtures import cube_mesh
import pytest


def test_how_many_groups_clamps():
    assert how_many_groups(100, 0) == 1
    assert how_many_groups(100, 1000) == 1
    assert how_many_groups(1000, 100) == 10
    assert how_many_groups(10 ** 9, 10) == C.REMESHER_NGRPS_MAX


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_grouped_adapt_conforming():
    vert, tet = cube_mesh(3)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, 0.3, m.vert.dtype)
    ne = len(tet)
    out, met2 = grouped_adapt(m, met, target_size=ne // 4, niter=2,
                              cycles=3)
    out = build_adjacency(out)
    assert check_adjacency(out) == {"asymmetric": 0, "face_mismatch": 0}
    vols = np.asarray(tet_volumes(out))[np.asarray(out.tmask)]
    assert (vols > 0).all()
    assert np.isclose(vols.sum(), 1.0, rtol=1e-4)
    q = np.asarray(tet_quality(out, met2))[np.asarray(out.tmask)]
    assert q.min() > 0.02


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_grouped_chunked_matches_unchunked(monkeypatch):
    """Chunked group dispatch (group_chunk: the memory-bounded
    dispatch) must produce the same mesh as one lax.map over all
    groups: the per-group program is identical, chunking only changes
    how many groups one dispatch covers, and the dead pad groups are
    no-ops."""
    from parmmg_tpu.parallel.groups import grouped_adapt_pass

    vert, tet = cube_mesh(3)

    def run():
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.full(m.capP, 0.35, m.vert.dtype)
        out, met2, _ = grouped_adapt_pass(m, met, 4, cycles=2)
        return out

    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "0")
    ref = run()
    # chunk=3 on 4 groups: pads to 6 with 2 dead groups
    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "3")
    chk = run()
    tm_r, tm_c = np.asarray(ref.tmask), np.asarray(chk.tmask)
    assert tm_r.sum() == tm_c.sum()
    assert (np.asarray(ref.tet)[tm_r] == np.asarray(chk.tet)[tm_c]).all()
    vr = np.asarray(ref.vert)[np.asarray(ref.vmask)]
    vc = np.asarray(chk.vert)[np.asarray(chk.vmask)]
    assert vr.shape == vc.shape and (vr == vc).all()


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_mesh_size_engages_groups():
    """Setting IParam.meshSize below the mesh size must route the
    single-device run through the grouped path."""
    from parmmg_tpu.api import ParMesh, IParam
    from parmmg_tpu.parallel import groups as G

    called = {"n": 0}
    orig = G.grouped_adapt

    def counting(*a, **k):
        called["n"] += 1
        return orig(*a, **k)

    G.grouped_adapt = counting
    try:
        vert, tet = cube_mesh(2)
        pm = ParMesh()
        pm.set_mesh_size(np_=len(vert), ne=len(tet))
        pm.set_vertices(vert)
        pm.set_tetrahedra(tet + 1)
        pm.set_met_size(1, len(vert))
        pm.set_scalar_mets(np.full(len(vert), 0.4))
        pm.set_iparameter(IParam.niter, 1)
        pm.set_iparameter(IParam.meshSize, len(tet) // 3)
        assert pm.run() == C.PMMG_SUCCESS
    finally:
        G.grouped_adapt = orig
    assert called["n"] == 1
    v, _ = pm.get_vertices()
    t, _ = pm.get_tetrahedra()
    p = v[t - 1]
    vol = np.einsum("ti,ti->t", p[:, 1] - p[:, 0],
                    np.cross(p[:, 2] - p[:, 0], p[:, 3] - p[:, 0])) / 6
    assert (vol > 0).all()
    assert np.isclose(vol.sum(), 1.0, rtol=1e-4)


def test_block_switches_match_static_cycles():
    """The grouped block is ONE program whose swap arm and split
    prescreen are run-time switches (ops/adapt ``do_swap``/``prescreen``
    traced): every cycle class gives the arrays the statically
    specialised cycle gives."""
    import jax
    from parmmg_tpu.core.mesh import MESH_FIELDS
    from parmmg_tpu.ops.adapt import adapt_cycle_impl
    from parmmg_tpu.ops.analysis import analyze_mesh
    vert, tet = cube_mesh(2)
    m = make_mesh(vert, tet, capP=6 * len(vert), capT=6 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, 0.3, m.vert.dtype)
    wave = jnp.asarray(2, jnp.int32)
    traced = jax.jit(lambda m, k, sw, pr: adapt_cycle_impl(
        m, k, wave, do_swap=sw, prescreen=pr))
    for sw, pr in ((True, False), (False, True)):
        ref = jax.jit(lambda m, k: adapt_cycle_impl(
            m, k, wave, do_swap=sw, prescreen=pr))(m, met)
        got = traced(m, met, jnp.asarray(sw), jnp.asarray(pr))
        assert np.array_equal(np.asarray(ref[2]), np.asarray(got[2]))
        assert int(np.asarray(ref[2])[0]) > 0        # it did split
        for f in MESH_FIELDS:
            assert np.array_equal(np.asarray(getattr(ref[0], f)),
                                  np.asarray(getattr(got[0], f))), f
        assert np.array_equal(np.asarray(ref[1]), np.asarray(got[1]))


def test_shard_capacity_rule():
    """distribute.shard_capacity / regrown_capacity — the one capacity
    rule: fresh capacities sit on compilecache.bucket's geometric
    ladder, a kept capacity stands exactly while every shard fits in it
    with REUSE_SLACK to grow and then moves up by the rung, and a regrow lands on a ladder rung at or
    above twice the old capacity (so it can meet a fresh split)."""
    from parmmg_tpu.parallel.distribute import (
        REUSE_SLACK, regrown_capacity, shard_capacity)
    from parmmg_tpu.utils.compilecache import bucket

    def rung(n):
        return bucket(n, floor=64, scheme="geo") == n

    capP, capT = shard_capacity(2900, 14400)
    assert (capP, capT) == (bucket(8700, floor=64, scheme="geo"),
                            bucket(43200, floor=64, scheme="geo"))
    assert rung(capP) and rung(capT)
    assert 3 * 14400 <= capT <= 1.5 * 3 * 14400 + 1
    # kept: the largest shard fits with the slack, in both dimensions
    fits = int(capT / REUSE_SLACK)
    assert shard_capacity(100, fits, keep=(capP, capT)) == (capP, capT)
    # not kept: one tet more, or too many vertices -> that column goes
    # to the next rung, the other stands (tests/test_capacity_keep.py
    # has the edges at the benchmark's own capacity)
    assert shard_capacity(100, fits + 1, keep=(capP, capT)) == \
        (capP, bucket(capT + 1, floor=64, scheme="geo"))
    assert shard_capacity(capP, 100, keep=(capP, capT)) == \
        (bucket(capP + 1, floor=64, scheme="geo"), capT)
    newP, newT = regrown_capacity(capP, capT)
    assert rung(newP) and rung(newT)
    assert 2 * capP <= newP <= 3 * capP + 2 and 2 * capT <= newT <= 3 * capT + 2
