"""The host tail's table engine (ops/topo_incr): the merge and its arms.

Tier-1 (fast, host-only) coverage: the tombstone-merge against a fresh
stable sort (the module's exactness proof, fuzzed with dead tets and
tombstones, with the jnp prefix sum and with the Pallas kernel
interpreted), the overflow fallback (a band narrower than the dirty
set), the nd==0 wholesale reuse, the Pallas prefix-sum kernel in
interpret mode, and that the knobs the cycle blocks once read are gone
(PR 46: a block sorts its tables in full).
"""
import os
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from parmmg_tpu.core.mesh import MESH_FIELDS, make_mesh
from parmmg_tpu.ops.topo_incr import (_INT32_MAX, incr_build_adjacency,
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


@pytest.mark.parametrize("pallas", [None, "1"], ids=["cumsum", "pallas"])
@pytest.mark.parametrize("ncols", [1, 2])
def test_merge_sorted_band_bit_equals_stable_sort(ncols, pallas,
                                                  monkeypatch):
    """``pallas``: PARMMG_TPU_PALLAS=1 puts the interpreted prefix-sum
    kernel inside the merge, which must leave it bit-equal."""
    rng = np.random.default_rng(1234 + ncols)
    if pallas is None:
        monkeypatch.delenv("PARMMG_TPU_PALLAS", raising=False)
    else:
        monkeypatch.setenv("PARMMG_TPU_PALLAS", pallas)
    # a function of this case's own: the prefix sum's dispatch reads the
    # setting when the merge is traced, and jit keeps a trace by function
    merge = jax.jit(lambda *args: merge_sorted_band(*args))
    for trial in range(25 if pallas is None else 6):
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
    # off a TPU the forced merge ran the kernel interpreted, the other
    # one ``jnp.cumsum``
    jaxpr = str(jax.make_jaxpr(lambda *args: merge_sorted_band(*args))(
        ks, jnp.asarray(order.astype(np.int32)), sd,
        [jnp.asarray(bkeys[:, j]) for j in range(ncols)],
        jnp.asarray(bslot)))
    assert ("interpret=True" in jaxpr) == (pallas is not None)
    assert ("cumsum" in jaxpr) == (pallas is None)


# ---- overflow fallback + nd==0 reuse on a real mesh -------------------------

@pytest.mark.parametrize("table", ["edges", "adjacency"])
def test_incr_overflow_falls_back_exact(table):
    """More dirty tets than the band: the lax.cond fallback must yield
    the same table a full rebuild does (exactness by construction)."""
    from parmmg_tpu.ops.adjacency import build_adjacency
    from parmmg_tpu.ops.edges import unique_edges
    m = _cube(2)
    if table == "edges":
        derive = partial(incr_unique_edges, shell_slots=0, band=(2,))
        ref = jax.jit(partial(unique_edges, shell_slots=0))(m)
    else:
        derive = partial(incr_build_adjacency, band=(2,))
        ref = jax.jit(build_adjacency)(m)
    out0, topo, off0 = derive(m, topo_init(m.capT))
    # dirty MANY tets (all live ones) without changing the mesh: the
    # band (width 2) overflows, the full rebuild re-derives the table
    topo_d = topo._replace(
        edirty=jnp.asarray(np.asarray(m.tmask)),
        fdirty=jnp.asarray(np.asarray(m.tmask)))
    out1, topo1, off1 = derive(m, topo_d)
    # neither came off a retained sort: none yet, then too many rows
    assert not bool(off0) and not bool(off1)
    for a, b, c in zip(jax.tree.leaves(out1), jax.tree.leaves(ref),
                       jax.tree.leaves(out0)):
        assert (np.asarray(a) == np.asarray(b)).all()
        assert (np.asarray(a) == np.asarray(c)).all()
    # the fallback refreshed the retained state: dirty cleared, ok set
    ok, dirty = ((topo1.eok, topo1.edirty) if table == "edges"
                 else (topo1.fok, topo1.fdirty))
    assert bool(ok) and int(np.asarray(dirty).sum()) == 0


def test_incr_nd0_reuses_retained_table():
    """A clean state (no dirty tets) must reproduce the table from the
    retained sort wholesale — and adjacency from the retained face
    sort — bit-identical to the legacy derivations."""
    from parmmg_tpu.ops.adjacency import build_adjacency
    from parmmg_tpu.ops.edges import unique_edges
    from parmmg_tpu.ops.topo_incr import polish_bands
    m = _cube(2)
    band = polish_bands(m.capT)
    et0, topo, off = incr_unique_edges(m, topo_init(m.capT),
                                       shell_slots=0, band=band)
    m1, topo, foff = incr_build_adjacency(m, topo, band=band)
    assert not bool(off) and not bool(foff)
    # second derivation, nothing dirty: the nd==0 reuse arm
    et1, _, off = incr_unique_edges(m, topo, shell_slots=0, band=band)
    m2, _, foff = incr_build_adjacency(m, topo, band=band)
    assert bool(off) and bool(foff)
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


# ---- the blocks' arm is gone (PR 46) ---------------------------------------

def test_no_incr_knob_is_declared_or_read():
    """Two settings chose and sized the cycle blocks' merge arm (the
    topology knob and its band): the registry declares neither and no
    file of the package, the scripts or the smoke names them."""
    from parmmg_tpu.api.knobs import KNOBS
    assert not [k for k in KNOBS if k.startswith("PARMMG_INCR")]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    named = []
    for top in ("parmmg_tpu", "scripts", "chip_smoke.py"):
        path = os.path.join(root, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith((".py", ".sh"))]
        for f in files:
            with open(f, encoding="utf-8") as fh:
                if "PARMMG_" + "INCR" in fh.read():
                    named.append(os.path.relpath(f, root))
    assert named == []
