"""Incremental topology engine tests (PARMMG_INCR_TOPO, ops/topo_incr).

Tier-1 (fast, host-only) coverage: the dirty-band width ladder, the
tombstone-merge against a fresh stable sort (the module's exactness
proof, fuzzed with dead tets and tombstones), the overflow fallback
(PARMMG_INCR_BAND forced below the dirty count), the nd==0 wholesale
reuse, and the Pallas prefix-sum kernel in interpret mode.  The slow
marks re-run the bit-parity claim through the full grouped pass —
polish included — knob on vs off, plus a forced-Pallas arm.
"""
import os
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from parmmg_tpu.core.mesh import MESH_FIELDS, make_mesh
from parmmg_tpu.ops.topo_incr import (_INT32_MAX, incr_band_width,
                                      incr_build_adjacency,
                                      incr_topo_enabled,
                                      incr_unique_edges,
                                      merge_sorted_band, topo_init)
from parmmg_tpu.utils.fixtures import cube_mesh


def _cube(n=2, capmul=4):
    from parmmg_tpu.ops.analysis import analyze_mesh
    vert, tet = cube_mesh(n)
    m = make_mesh(vert, tet, capP=capmul * len(vert),
                  capT=capmul * len(tet))
    return analyze_mesh(m).mesh


def _assert_mesh_equal(a, b, label=""):
    for f in MESH_FIELDS:
        av, bv = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert (av == bv).all(), f"{label}: mesh field {f} differs"


# ---- band width ladder ------------------------------------------------------

def test_incr_band_width_ladder(monkeypatch):
    from parmmg_tpu.utils.compilecache import bucket
    monkeypatch.delenv("PARMMG_INCR_BAND", raising=False)
    # the band width IS a rung of the shared geo bucket ladder — band
    # sizing can never mint a new shape family
    for capT in (64, 1024, 9216, 98304, 1 << 20):
        B = incr_band_width(capT)
        assert B == bucket(max(1, capT // 16), floor=1024, scheme="geo",
                           cap=capT)
        assert 1 <= B <= capT
    # tiny meshes: the ladder reaches capT (band == full width)
    assert incr_band_width(64) == 64
    # big meshes: strict compaction
    assert incr_band_width(1 << 20) < (1 << 20)
    # monotone in capT (no oscillating families across regrows)
    widths = [incr_band_width(c) for c in range(64, 40000, 64)]
    assert all(a <= b for a, b in zip(widths, widths[1:]))
    # the override clamps into [1, capT]
    monkeypatch.setenv("PARMMG_INCR_BAND", "7")
    assert incr_band_width(9216) == 7
    monkeypatch.setenv("PARMMG_INCR_BAND", "999999")
    assert incr_band_width(64) == 64


def test_incr_knob_defaults_off(monkeypatch):
    monkeypatch.delenv("PARMMG_INCR_TOPO", raising=False)
    assert incr_topo_enabled() is False, \
        "PARMMG_INCR_TOPO must default off (exact legacy path)"
    monkeypatch.setenv("PARMMG_INCR_TOPO", "1")
    assert incr_topo_enabled() is True
    monkeypatch.setenv("PARMMG_INCR_TOPO", "0")
    assert incr_topo_enabled() is False


# ---- tombstone merge vs fresh stable sort -----------------------------------

def _merge_case(rng, ncols, n, slots_per_tet=3):
    """One fuzz case: retained stable sort of old keys, a dirty set
    re-keyed (tombstones: dirty DEAD slots key to INT32_MAX but keep
    their real slot id), band padded with (MAX, MAX) rows."""
    ntet = n // slots_per_tet
    kmax = 50
    old = rng.integers(0, kmax, size=(n, ncols)).astype(np.int32)
    old[rng.random(n) < 0.15] = _INT32_MAX          # dead slots
    # stable sort by (key..., slot): slot ascending IS the stable tie
    order = np.lexsort(tuple(old[:, j] for j in range(ncols))[::-1]) \
        if ncols > 1 else np.argsort(old[:, 0], kind="stable")
    dirty_tets = rng.random(ntet) < 0.4
    dirty_slot = np.repeat(dirty_tets, slots_per_tet)
    new = old.copy()
    fresh = rng.integers(0, kmax, size=(n, ncols)).astype(np.int32)
    fresh[rng.random(n) < 0.3] = _INT32_MAX         # tombstones
    new[dirty_slot] = fresh[dirty_slot]
    # band: every slot of every dirty tet, padded to B
    didx = np.flatnonzero(dirty_slot).astype(np.int32)
    B = len(didx) + int(rng.integers(0, 5))
    bslot = np.full(B, _INT32_MAX, np.int32)
    bslot[: len(didx)] = didx
    bkeys = np.full((B, ncols), _INT32_MAX, np.int32)
    bkeys[: len(didx)] = new[didx]
    return old, new, order, dirty_slot, bkeys, bslot


@pytest.mark.parametrize("ncols", [1, 2])
def test_merge_sorted_band_bit_equals_stable_sort(ncols):
    rng = np.random.default_rng(1234 + ncols)
    merge = jax.jit(merge_sorted_band)
    for trial in range(25):
        n = int(rng.integers(6, 120)) // 3 * 3 or 3
        old, new, order, dmask, bkeys, bslot = _merge_case(rng, ncols, n)
        ks = [jnp.asarray(old[order, j]) for j in range(ncols)]
        sd = jnp.asarray(dmask[order])
        mk, ms = merge(ks, jnp.asarray(order.astype(np.int32)), sd,
                       [jnp.asarray(bkeys[:, j]) for j in range(ncols)],
                       jnp.asarray(bslot))
        # reference: fresh stable sort of the NEW keys
        ref = np.lexsort(tuple(new[:, j] for j in range(ncols))[::-1]) \
            if ncols > 1 else np.argsort(new[:, 0], kind="stable")
        assert (np.asarray(ms) == ref).all(), \
            f"trial {trial}: merged permutation != fresh stable sort"
        for j in range(ncols):
            assert (np.asarray(mk[j]) == new[ref, j]).all(), \
                f"trial {trial}: merged key col {j} differs"


# ---- overflow fallback + nd==0 reuse on a real mesh -------------------------

def test_incr_overflow_falls_back_exact(monkeypatch):
    """More dirty tets than the band: the lax.cond fallback must yield
    the same table a full rebuild does (exactness by construction)."""
    from parmmg_tpu.ops.edges import unique_edges
    monkeypatch.setenv("PARMMG_INCR_BAND", "2")     # force overflow
    m = _cube(2)
    on = jnp.ones((), bool)

    def derive(mesh, topo):
        et, topo = incr_unique_edges(mesh, topo, on, shell_slots=0)
        return et, topo
    jderive = jax.jit(derive)
    et0, topo = jderive(m, topo_init(m.capT))
    # dirty MANY tets (all live ones) without changing the mesh: the
    # band (width 2) overflows, the full rebuild re-derives the table
    topo_d = topo._replace(
        edirty=jnp.asarray(np.asarray(m.tmask)),
        fdirty=jnp.asarray(np.asarray(m.tmask)))
    et1, topo1 = jderive(m, topo_d)
    ref = jax.jit(partial(unique_edges, shell_slots=0))(m)
    for a, b, c in zip(jax.tree.leaves(et1), jax.tree.leaves(ref),
                       jax.tree.leaves(et0)):
        assert (np.asarray(a) == np.asarray(b)).all()
        assert (np.asarray(a) == np.asarray(c)).all()
    # the fallback refreshed the retained state: dirty cleared, ok set
    assert bool(topo1.eok) and int(np.asarray(topo1.edirty).sum()) == 0


def test_incr_nd0_reuses_retained_table():
    """A clean state (no dirty tets) must reproduce the table from the
    retained sort wholesale — and adjacency from the retained face
    sort — bit-identical to the legacy derivations."""
    from parmmg_tpu.ops.adjacency import build_adjacency
    from parmmg_tpu.ops.edges import unique_edges
    m = _cube(2)
    on = jnp.ones((), bool)
    jedge = jax.jit(lambda mm, t: incr_unique_edges(mm, t, on,
                                                    shell_slots=0))
    jadj = jax.jit(lambda mm, t: incr_build_adjacency(mm, t, on))
    et0, topo = jedge(m, topo_init(m.capT))
    m1, topo = jadj(m, topo)
    # second derivation, nothing dirty: the nd==0 reuse arm
    et1, _ = jedge(m, topo)
    m2, _ = jadj(m, topo)
    ref_et = jax.jit(partial(unique_edges, shell_slots=0))(m)
    ref_m = jax.jit(build_adjacency)(m)
    for a, b in zip(jax.tree.leaves(et1), jax.tree.leaves(ref_et)):
        assert (np.asarray(a) == np.asarray(b)).all()
    _assert_mesh_equal(m1, ref_m, "incr adjacency (first derivation)")
    _assert_mesh_equal(m2, ref_m, "incr adjacency (nd==0 reuse)")


# ---- Pallas prefix kernel ---------------------------------------------------

def test_merge_prefix_pallas_interpret_parity():
    from parmmg_tpu.ops.pallas_kernels import merge_prefix_pallas
    rng = np.random.default_rng(77)
    for n in (1, 127, 128, 1024, 1025, 6144):
        x = jnp.asarray(rng.integers(0, 3, n).astype(np.int32))
        got = merge_prefix_pallas(x, interpret=True)
        ref = jnp.cumsum(x)
        assert got.dtype == jnp.int32
        assert (np.asarray(got) == np.asarray(ref)).all(), n


# ---- slow: full grouped bit-parity, knob on vs off --------------------------

@pytest.mark.slow
def test_grouped_incr_knob_parity(monkeypatch):
    """PARMMG_INCR_TOPO on/off through the full grouped pass — waves,
    cycle blocks, regrows AND the sliver polish phase — is bit-for-bit
    identical, with identical op counters."""
    from parmmg_tpu.ops.adapt import AdaptStats
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.groups import grouped_adapt
    vert, tet = cube_mesh(2)
    outs = []
    for env in ("0", "1"):
        monkeypatch.setenv("PARMMG_INCR_TOPO", env)
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.full(m.capP, 0.35, m.vert.dtype)
        st = AdaptStats()
        mo, ko = grouped_adapt(m, met, 16, niter=2, cycles=3, stats=st)
        outs.append((mo, ko, st))
    (m0, k0, s0), (m1, k1, s1) = outs
    _assert_mesh_equal(m0, m1, "incr grouped")
    assert (np.asarray(k0) == np.asarray(k1)).all()
    assert (s0.nsplit, s0.ncollapse, s0.nswap, s0.nmoved) == \
        (s1.nsplit, s1.ncollapse, s1.nswap, s1.nmoved)
    assert s0.cycles == s1.cycles
    # the knob-on run recorded its dirty-band trajectory
    assert "incr_dirty_per_cycle" in s1.sched_extra
    assert len(s1.sched_extra["incr_dirty_per_cycle"]) > 0


@pytest.mark.slow
def test_incr_forced_pallas_parity(monkeypatch):
    """PARMMG_TPU_PALLAS=1 (interpret-mode merge_prefix inside the
    band merge) leaves the incremental derivations bit-identical."""
    from parmmg_tpu.ops.adapt import adapt_cycle_impl
    m = _cube(2)
    met = jnp.full(m.capP, 0.5, m.vert.dtype)
    on = jnp.ones((), bool)
    outs = []
    for env in (None, "1"):
        if env is None:
            monkeypatch.delenv("PARMMG_TPU_PALLAS", raising=False)
        else:
            monkeypatch.setenv("PARMMG_TPU_PALLAS", env)
        # fresh trace per arm: the dispatch reads the env at trace time
        step = jax.jit(lambda mm, kk, ww, tt: adapt_cycle_impl(
            mm, kk, ww, topo=tt, incr=on))
        mm, kk, tt = m, met, topo_init(m.capT)
        for cyc in range(3):
            mm, kk, cnt, tt = step(mm, kk, jnp.asarray(cyc, jnp.int32),
                                   tt)
        outs.append((mm, kk, cnt))
    (ma, ka, ca), (mb, kb, cb) = outs
    _assert_mesh_equal(ma, mb, "incr forced-pallas")
    assert (np.asarray(ka) == np.asarray(kb)).all()
    assert (np.asarray(ca) == np.asarray(cb)).all()
