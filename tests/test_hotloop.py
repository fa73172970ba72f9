"""Hot-loop attack tests (README "Hot-loop cycle costs" section).

Tier-1 (fast) coverage: the face-pair-from-sort table against the
legacy ``adja`` pairing, the donor-band width math, the fused top-k
scoring prep (jnp reference AND interpret-mode Pallas kernels).  The
slow marks re-run the bit-parity claims through the full waves per knob — including the
polish pass — exactly as the production drivers call them.
"""
import os
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from parmmg_tpu.core.mesh import MESH_FIELDS, make_mesh
from parmmg_tpu.ops.adjacency import build_adjacency
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


# ---- donor-band width math (attack 2) ---------------------------------------

def test_collapse_band_width_ladder():
    from parmmg_tpu.ops.collapse import collapse_band_width
    from parmmg_tpu.utils.compilecache import bucket

    # the band width IS a rung of the shared geo bucket ladder — no new
    # shape family can come out of it
    for capT in (64, 256, 1024, 4096, 12288, 100000):
        B = collapse_band_width(capT)
        assert B == bucket(max(1, capT // 4), floor=256, scheme="geo",
                           cap=capT)
        assert B <= capT
    # tiny meshes: the ladder reaches capT and the full path is taken
    assert collapse_band_width(64) == 64
    assert collapse_band_width(256) == 256
    # big meshes: the band is a strict compaction
    assert collapse_band_width(12288) < 12288
    # monotone in capT (no oscillating shape families across regrows)
    widths = [collapse_band_width(c) for c in range(64, 20000, 64)]
    assert all(a <= b for a, b in zip(widths, widths[1:]))


# ---- fused top-k scoring prep (attack 4) ------------------------------------

def _prep_ref(c, v):
    return jnp.where(c, -v, -jnp.inf), jnp.sum(c.astype(jnp.int32))


def test_topk_prep_matches_inline(monkeypatch):
    from parmmg_tpu.ops.edges import topk_prep, topk_prep3
    rng = np.random.default_rng(7)
    c = jnp.asarray(rng.random(777) > 0.6)
    v0, v1, v2 = (jnp.asarray(rng.random(777).astype(np.float32))
                  for _ in range(3))
    for env in (None, "1"):
        if env is None:
            monkeypatch.delenv("PARMMG_TPU_PALLAS", raising=False)
        else:
            # forced mode: the off-TPU branch runs the interpret-mode
            # Pallas kernels — must still be bit-identical
            monkeypatch.setenv("PARMMG_TPU_PALLAS", env)
        neg, n = topk_prep(c, v0)
        rneg, rn = _prep_ref(c, v0)
        assert (np.asarray(neg) == np.asarray(rneg)).all(), env
        assert int(n) == int(rn)
        neg3, n3 = topk_prep3(c, v0, v1, v2)
        # exact legacy association order: min(v0, min(v1, v2))
        rneg3, rn3 = _prep_ref(c, jnp.minimum(v0, jnp.minimum(v1, v2)))
        assert (np.asarray(neg3) == np.asarray(rneg3)).all(), env
        assert int(n3) == int(rn3)


def test_score_kernels_interpret_parity():
    from parmmg_tpu.ops.pallas_kernels import (score3_count_pallas,
                                               score_count_pallas)
    rng = np.random.default_rng(11)
    for n in (1, 127, 128, 1000):
        v = jnp.asarray(rng.random(n).astype(np.float32))
        for mask in (rng.random(n) > 0.5, np.zeros(n, bool),
                     np.ones(n, bool)):
            c = jnp.asarray(mask)
            neg, cnt = score_count_pallas(c.astype(jnp.float32), v,
                                          interpret=True)
            rneg, rcnt = _prep_ref(c, v)
            assert (np.asarray(neg) == np.asarray(rneg)).all()
            assert int(cnt) == int(rcnt) == int(mask.sum())
        v1 = jnp.asarray(rng.random(n).astype(np.float32))
        v2 = jnp.asarray(rng.random(n).astype(np.float32))
        c = jnp.asarray(rng.random(n) > 0.3)
        neg3, cnt3 = score3_count_pallas(c.astype(jnp.float32), v, v1,
                                         v2, interpret=True)
        rneg3, rcnt3 = _prep_ref(c, jnp.minimum(v, jnp.minimum(v1, v2)))
        assert (np.asarray(neg3) == np.asarray(rneg3)).all()
        assert int(cnt3) == int(rcnt3)


# ---- face-pair table off the sort (attack 1) --------------------------------

def test_face_pairs_match_adja():
    from parmmg_tpu.ops.quality import quality_from_points
    from parmmg_tpu.ops.swap import (_met6, _pair_fields_adja,
                                     _pair_fields_facesort)
    for m in (_cube(2), _cube(3)):
        m = build_adjacency(m)
        met = jnp.full(m.capP, 0.8, m.vert.dtype)
        m6 = _met6(met)
        q_tet = quality_from_points(
            m.vert[m.tet], None if m6 is None else m6[m.tet])
        ref = _pair_fields_adja(m, q_tet, m.capT)
        m2, *got = _pair_fields_facesort(m, q_tet, m.capT)
        # the candidate set must agree EVERYWHERE; t2/f2 carry dead
        # fill on non-candidate rows (different fill per path, never
        # consumed: every downstream read in swap23_wave is gated by
        # cand — q_pair, the fan construction and all scatters)
        cand = np.asarray(ref[3])
        assert (cand == np.asarray(got[3])).all(), \
            "facesort candidate set differs from adja pairing"
        assert (np.asarray(got[0]) == np.asarray(ref[0])).all(), \
            "facesort fstar differs from adja pairing"
        for name, a, b in zip(("t2", "f2"), got[1:], ref[1:]):
            assert (np.asarray(a)[cand] == np.asarray(b)[cand]).all(), \
                f"facesort pair field {name} differs on candidate rows"
        # the MG_BDY replay off the same sort is idempotent on a mesh
        # whose tags build_adjacency already set
        _assert_mesh_equal(m2, m, "bdy-tag replay")


def test_knob_readers_default_on(monkeypatch):
    from parmmg_tpu.ops.pallas_kernels import pallas_score_enabled
    from parmmg_tpu.ops.swap import swap_facesort_enabled
    monkeypatch.delenv("PARMMG_PALLAS_SCORE", raising=False)
    assert pallas_score_enabled() is True, "must default on"
    monkeypatch.setenv("PARMMG_PALLAS_SCORE", "0")
    assert pallas_score_enabled() is False
    monkeypatch.setenv("PARMMG_PALLAS_SCORE", "1")
    assert pallas_score_enabled() is True
    # facesort defaults platform-aware: on iff the program is placed on
    # a TPU (the CPU sort costs more than the adja rebuild it replaces);
    # explicit 1/0 force either path on any backend
    monkeypatch.delenv("PARMMG_SWAP_FACESORT", raising=False)
    assert swap_facesort_enabled() is (jax.default_backend() == "tpu")
    # a TPU process stages its tail on the host's CPU backend: what
    # counts is where the program is placed, not the process default
    from parmmg_tpu.utils.placement import host_staging
    with monkeypatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        assert swap_facesort_enabled() is True
        with host_staging():
            assert swap_facesort_enabled() is False
    monkeypatch.setenv("PARMMG_SWAP_FACESORT", "0")
    assert swap_facesort_enabled() is False
    monkeypatch.setenv("PARMMG_SWAP_FACESORT", "1")
    assert swap_facesort_enabled() is True


# ---- slow per-knob wave parity ----------------------------------------------

@pytest.mark.slow
def test_facesort_knob_parity(monkeypatch):
    """PARMMG_SWAP_FACESORT on/off through the full adaptation cycle
    AND the sliver polish pass (polish-on) is bit-for-bit identical."""
    from parmmg_tpu.ops.adapt import adapt_cycle_impl, sliver_polish_impl
    m = _cube(2)
    met = jnp.full(m.capP, 0.6, m.vert.dtype)
    outs = []
    for env in ("0", "1"):
        monkeypatch.setenv("PARMMG_SWAP_FACESORT", env)
        # fresh partial per arm: a fresh trace re-reads the env knob
        cyc = jax.jit(partial(adapt_cycle_impl, do_swap=True))
        mm, kk, cc = cyc(m, met, jnp.asarray(0, jnp.int32))
        pol = jax.jit(partial(sliver_polish_impl))
        mp, cp = pol(mm, kk, jnp.asarray(100, jnp.int32))
        outs.append((mm, kk, cc, mp, cp))
    (m0, k0, c0, p0, q0), (m1, k1, c1, p1, q1) = outs
    _assert_mesh_equal(m0, m1, "facesort cycle")
    assert (np.asarray(k0) == np.asarray(k1)).all()
    assert (np.asarray(c0) == np.asarray(c1)).all()
    _assert_mesh_equal(p0, p1, "facesort polish")
    # the last column says whether the exit adjacency was rebuilt: always
    # on the face-sort path, only after a 2-3 swap on the other
    assert (np.asarray(q0)[:8] == np.asarray(q1)[:8]).all()


@pytest.mark.slow
def test_collapse_band_knob_parity(monkeypatch):
    """PARMMG_COLLAPSE_BAND on/off through collapse waves that engage
    the band (B < capT) is bit-for-bit identical."""
    from parmmg_tpu.ops.collapse import collapse_band_width, collapse_wave
    m0 = _cube(3, capmul=8)
    assert collapse_band_width(m0.capT) < m0.capT, \
        "fixture too small: the band is not engaged"
    met = jnp.full(m0.capP, 2.0)         # everything is "too short"
    states = []
    for env in ("0", "1"):
        monkeypatch.setenv("PARMMG_COLLAPSE_BAND", env)
        m = m0
        ns = []
        for _ in range(4):
            wave = jax.jit(partial(collapse_wave))
            res = wave(m, met)
            m = build_adjacency(res.mesh)
            ns.append(int(res.ncollapse))
        states.append((m, ns))
    (ma, na), (mb, nb) = states
    assert na == nb and sum(na) > 0, (na, nb)
    _assert_mesh_equal(ma, mb, "collapse band")


@pytest.mark.slow
def test_pallas_forced_wave_parity(monkeypatch):
    """PARMMG_TPU_PALLAS=1 (forced interpret-mode kernels inside
    topk_prep) leaves split/collapse/swap waves bit-identical."""
    from parmmg_tpu.ops.collapse import collapse_wave
    from parmmg_tpu.ops.split import split_wave
    from parmmg_tpu.ops.swap import swap23_wave
    m = build_adjacency(_cube(2))
    met_s = jnp.full(m.capP, 0.3, m.vert.dtype)   # split-rich
    met_c = jnp.full(m.capP, 2.0, m.vert.dtype)   # collapse-rich
    outs = []
    for env in (None, "1"):
        if env is None:
            monkeypatch.delenv("PARMMG_TPU_PALLAS", raising=False)
        else:
            monkeypatch.setenv("PARMMG_TPU_PALLAS", env)
        sp = jax.jit(partial(split_wave))(m, met_s)
        co = jax.jit(partial(collapse_wave))(m, met_c)
        sw = jax.jit(partial(swap23_wave))(m, met_s)
        outs.append((sp, co, sw))
    a, b = outs
    for name, ra, rb in zip(("split", "collapse", "swap23"), a, b):
        _assert_mesh_equal(ra.mesh, rb.mesh, f"pallas-forced {name}")
