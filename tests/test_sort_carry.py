"""A sort hands back what it sorted (PR 45): the table makers take their
sorted keys and payloads out of the sort itself and read a sorted
neighbour by a shift.  Every function here is held, to the bit, to the
formulation it replaced, which this file keeps as its plain reference
(``argsort`` / ``lexsort`` and the fetches through the permutation
written out), on meshes with dead rows, duplicate keys and a face three
tets share, in both ``PACK_LIMIT`` branches, and where a table goes back
into slot order (``edges.unsort``) by the sort a program placed on a TPU
traces (``on_tpu``) and by the scatter the host traces.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parmmg_tpu.core.constants import MG_BDY, MG_GEO, MG_REF, MG_REQ
from parmmg_tpu.core.mesh import (make_mesh, tet_edge_vertices,
                                  tet_face_vertices)
from parmmg_tpu.ops import adjacency as adj
from parmmg_tpu.ops import collapse, edges, swap
from parmmg_tpu.parallel import groups
from parmmg_tpu.utils import placement
from parmmg_tpu.utils.fixtures import cube_mesh

I32_MAX = 2147483647
ON_TPU = [True, False]
PACKED = [True, False]


@pytest.fixture
def placed(monkeypatch):
    """Steer what ``edges.unsort`` observes: ``placed(True)`` is a
    program placed on a TPU."""
    def steer(on):
        monkeypatch.setattr(placement, "placed_on_tpu", lambda: on)
    return steer


@pytest.fixture
def branch(monkeypatch):
    """``branch(False)`` sends every table maker down its unpacked
    branch (``capP > PACK_LIMIT``) at a toy capacity."""
    def steer(packed):
        if not packed:
            monkeypatch.setattr(edges, "PACK_LIMIT", 0)
    return steer


def awkward_mesh(seed=3):
    """``cube_mesh(3)`` with tags, in a capacity half empty; a fifth of
    its tets dead, and one live tet stored twice (every face of it is
    shared by three tets: a non-manifold face)."""
    rng = np.random.default_rng(seed)
    vert, tet = cube_mesh(3)
    tet = np.concatenate([tet, tet[7:8]])
    m = make_mesh(vert, tet, capP=2 * len(vert), capT=2 * len(tet))
    tmask = np.asarray(m.tmask).copy()
    tmask[rng.choice(len(tet) - 1, len(tet) // 5, replace=False)] = False
    tmask[7] = True
    bits = np.array([0, MG_BDY, MG_REF, MG_GEO | MG_REQ], np.uint32)
    return dataclasses.replace(
        m, tmask=jnp.asarray(tmask),
        etag=jnp.asarray(bits[rng.integers(0, 4, (m.capT, 6))]),
        ftag=jnp.asarray(bits[rng.integers(0, 4, (m.capT, 4))]
                         * (rng.random((m.capT, 4)) < 0.3)),
        fref=jnp.asarray(rng.integers(0, 5, (m.capT, 4)), jnp.int32))


def same(got, ref):
    got, ref = jax.tree.leaves(got), jax.tree.leaves(ref)
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        g, r = np.asarray(g), np.asarray(r)
        assert g.dtype == r.dtype and g.shape == r.shape
        assert np.array_equal(g, r)


# ---- the formulations the program had (commit 8248ef4), written out ---------

def old_sort_pairs(a, b, valid, capP, packed):
    if packed:
        key = jnp.where(valid, a * capP + b, I32_MAX)
        order = jnp.argsort(key)
        ks = key[order]
        first = edges.segment_first((ks,))
        inv = ks == I32_MAX
        return (order, jnp.where(inv, I32_MAX, ks // capP),
                jnp.where(inv, I32_MAX, ks % capP), first)
    aa = jnp.where(valid, a, I32_MAX)
    bb = jnp.where(valid, b, I32_MAX)
    order = jnp.lexsort((bb, aa))
    ka, kb = aa[order], bb[order]
    return order, ka, kb, edges.segment_first((ka, kb))


def old_unique_edges(mesh, shell_slots, packed):
    capT = mesh.capT
    n6 = capT * 6
    ev = tet_edge_vertices(mesh.tet).reshape(n6, 2)
    order, ka, kb, first = old_sort_pairs(
        jnp.minimum(ev[:, 0], ev[:, 1]), jnp.maximum(ev[:, 0], ev[:, 1]),
        jnp.repeat(mesh.tmask, 6), mesh.capP, packed)
    valid_s = ka != I32_MAX
    pos = jnp.arange(n6)
    tags = jnp.where(valid_s, mesh.etag.reshape(n6)[order], 0)

    def comb(pa, pb):
        fa, ha, va = pa
        fb, hb, vb = pb
        return (fa | fb, jnp.where(fb, hb, jnp.maximum(ha, hb)),
                jnp.where(fb, vb, va | vb))

    _, seg_head, or_scan = jax.lax.associative_scan(
        comb, (first, jnp.where(first, pos, 0), tags))
    rank = pos - seg_head
    is_last = jnp.concatenate([first[1:], jnp.array([True])])
    head_tbl = jnp.zeros((n6, 2), jnp.int32).at[
        jnp.where(is_last, seg_head, n6)].set(
        jnp.stack([or_scan.astype(jnp.int32),
                   (rank + 1).astype(jnp.int32)], axis=1),
        mode="drop", unique_indices=True)
    back = jnp.zeros((n6, 2), jnp.int32).at[order].set(
        jnp.stack([seg_head.astype(jnp.int32), rank.astype(jnp.int32)],
                  axis=1), unique_indices=True)
    if shell_slots > 0:
        shell3 = jnp.full((n6, shell_slots), -1, jnp.int32).at[
            jnp.where(valid_s & (rank < shell_slots), seg_head, n6),
            jnp.clip(rank, 0, shell_slots - 1)].set(
            (order // 6).astype(jnp.int32), mode="drop",
            unique_indices=True)
    else:
        shell3 = jnp.zeros((n6, 0), jnp.int32)
    if shell_slots > 0 and packed:
        skey = jnp.where(valid_s, ka * mesh.capP + kb, I32_MAX)
    else:
        skey = jnp.zeros((0,), jnp.int32)
    return edges.EdgeTable(
        ev=jnp.stack([ka, kb], axis=1), emask=first & valid_s,
        etag=head_tbl[:, 0].astype(jnp.uint32), nshell=head_tbl[:, 1],
        edge_id=back[:, 0].reshape(capT, 6), shell3=shell3,
        shell_rank=back[:, 1].reshape(capT, 6), skey=skey)


def old_face_sort(mesh, packed):
    """(t, f, partner, matched, valid_s)."""
    capT = mesh.capT
    fv = jnp.sort(tet_face_vertices(mesh.tet).reshape(capT * 4, 3), axis=1)
    cols = jnp.where(~jnp.repeat(mesh.tmask, 4)[:, None], I32_MAX, fv)
    tetid = jnp.repeat(jnp.arange(capT, dtype=jnp.int32), 4)
    faceid = jnp.tile(jnp.arange(4, dtype=jnp.int32), capT)
    if packed:
        w = jnp.where(cols[:, 0] == I32_MAX, I32_MAX,
                      cols[:, 1] * mesh.capP + cols[:, 2])
        order = jnp.lexsort((w, cols[:, 0]))
        k = jnp.stack([cols[order, 0], w[order]], axis=1)
    else:
        order = jnp.lexsort((cols[:, 2], cols[:, 1], cols[:, 0]))
        k = cols[order]
    t, f = tetid[order], faceid[order]
    first = edges.segment_first(tuple(k[:, j] for j in range(k.shape[1])))
    eq_next = ~first[1:] & (k[:-1, 0] != I32_MAX)
    same_next = jnp.concatenate([eq_next, jnp.array([False])])
    same_prev = jnp.concatenate([jnp.array([False]), eq_next])
    idx = jnp.arange(capT * 4)
    partner = jnp.where(same_next, idx + 1,
                        jnp.where(same_prev, idx - 1, idx))
    return t, f, partner, same_next | same_prev, k[:, 0] != I32_MAX


def old_build_adjacency(mesh, packed):
    t, f, partner, matched, _ = old_face_sort(mesh, packed)
    adj_val = jnp.where(matched, 4 * t[partner] + f[partner], -1)
    adja = jnp.full((mesh.capT, 4), -1, jnp.int32).at[t, f].set(
        adj_val.astype(jnp.int32), unique_indices=True)
    adja = jnp.where(mesh.tmask[:, None], adja, -1)
    is_bdy = (adja < 0) & mesh.tmask[:, None]
    return dataclasses.replace(
        mesh, adja=adja, ftag=jnp.where(is_bdy, mesh.ftag | MG_BDY,
                                        mesh.ftag))


def old_pair_fields_facesort(mesh, q_tet, capT, packed):
    t, f, partner, matched, valid_s = old_face_sort(mesh, packed)
    mesh = adj.bdy_tags_from_sort(mesh, t, f, matched, valid_s)
    tp = t[partner]
    fp = f[partner]
    own_s = matched & (t < tp) & (mesh.ftag[t, f] == 0) & \
        (mesh.ftag[tp, fp] == 0)
    is_star, _, _ = edges.scatter_argmax2(t, -q_tet[tp], -f, own_s, capT)
    tbl = jnp.zeros((capT, 3), jnp.int32).at[
        jnp.where(is_star, t, capT)].set(
        jnp.stack([f, tp, fp], axis=1), mode="drop", unique_indices=True)
    cand_full = jnp.zeros(capT + 1, bool).at[
        jnp.where(own_s, t, capT)].max(own_s, mode="drop")[:capT]
    return mesh, tbl[:, 0], tbl[:, 1], tbl[:, 2], cand_full


def old_tag_joins_core(new_tet, ftag, fref, etag, donor, recv, capP,
                       packed):
    n = new_tet.shape[0]
    F4 = n * 4
    fvn = jnp.sort(tet_face_vertices(new_tet).reshape(F4, 3), axis=1)
    donor_f = jnp.repeat(donor, 4)
    rel_f = donor_f | jnp.repeat(recv, 4)
    if packed:
        w_f = jnp.where(rel_f, fvn[:, 1] * capP + fvn[:, 2], I32_MAX)
        k0_f = jnp.where(rel_f, fvn[:, 0], I32_MAX)
        order_f = jnp.lexsort((w_f, k0_f))
        ks = (k0_f[order_f], w_f[order_f])
    else:
        c = [jnp.where(rel_f, fvn[:, j], I32_MAX) for j in range(3)]
        order_f = jnp.lexsort((c[2], c[1], c[0]))
        ks = tuple(cj[order_f] for cj in c)
    first_f = edges.segment_first(ks)
    seg_f = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first_f, jnp.arange(F4), 0))
    is_last_f = jnp.concatenate([first_f[1:], jnp.array([True])])
    dtag_f = jnp.where(donor_f[order_f], ftag.reshape(F4)[order_f], 0)
    tot_tag = jnp.zeros(F4, jnp.uint32).at[
        jnp.where(is_last_f, seg_f, F4)].set(
        edges.segmented_or(first_f, dtag_f), mode="drop",
        unique_indices=True)
    add_tag = jnp.zeros(F4, jnp.uint32).at[order_f].set(
        tot_tag[seg_f], unique_indices=True).reshape(n, 4)
    dref_f = jnp.where(donor_f[order_f], fref.reshape(F4)[order_f], 0)
    tot_ref = jnp.zeros(F4, jnp.int32).at[
        jnp.where(is_last_f, seg_f, F4)].set(
        edges.segmented_max(first_f, dref_f), mode="drop",
        unique_indices=True)
    add_ref = jnp.zeros(F4, jnp.int32).at[order_f].set(
        tot_ref[seg_f], unique_indices=True).reshape(n, 4)
    ev_new = tet_edge_vertices(new_tet).reshape(n * 6, 2)
    donor_s = jnp.repeat(donor, 6)
    order, _, _, first = old_sort_pairs(
        jnp.minimum(ev_new[:, 0], ev_new[:, 1]),
        jnp.maximum(ev_new[:, 0], ev_new[:, 1]),
        jnp.repeat(recv, 6) | donor_s, capP, packed)
    seg = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first, jnp.arange(n * 6), 0))
    dtag = jnp.where(donor_s[order], etag.reshape(n * 6)[order], 0)
    is_last = jnp.concatenate([first[1:], jnp.array([True])])
    total = jnp.zeros(n * 6, jnp.uint32).at[
        jnp.where(is_last, seg, n * 6)].set(
        edges.segmented_or(first, dtag), mode="drop", unique_indices=True)
    add_e = jnp.zeros(n * 6, jnp.uint32).at[order].set(
        total[seg], unique_indices=True).reshape(n, 6)
    return add_tag, add_ref, add_e


# ---- the sort itself --------------------------------------------------------

@pytest.mark.parametrize("nkeys", [1, 2, 3])
def test_sort_carry_is_the_argsort_and_its_fetches(nkeys):
    rng = np.random.default_rng(5)
    n = 1500
    keys = [jnp.asarray(np.where(rng.random(n) < 0.2, I32_MAX,
                                 rng.integers(0, 7, n)), jnp.int32)
            for _ in range(nkeys)]
    pays = (jnp.asarray(rng.integers(0, 2 ** 32, n, dtype=np.uint64)
                        .astype(np.uint32)),
            jnp.asarray(rng.integers(-9, 9, n), jnp.int32),
            jnp.asarray(rng.random(n) < 0.5))
    order, ks, ps = edges.sort_carry(tuple(keys), pays)
    ref = jnp.argsort(keys[0]) if nkeys == 1 \
        else jnp.lexsort(tuple(reversed(keys)))
    same(order, ref)
    same(ks, tuple(k[ref] for k in keys))
    same(ps, tuple(p[ref] for p in pays))
    # equal keys stay in slot order (the shells' ranks rest on it)
    assert len(np.unique(np.asarray(order))) == n


def test_a_sort_that_carries_fetches_nothing():
    key = jnp.zeros(64, jnp.int32)
    pay = (jnp.zeros(64, jnp.uint32), jnp.zeros(64, jnp.int32))
    text = str(jax.make_jaxpr(
        lambda k, p: edges.sort_carry((k,), p))(key, pay))
    assert "gather" not in text and text.count(" sort[") == 1


@pytest.mark.parametrize("on_tpu", ON_TPU)
def test_the_way_back_is_the_scatter(placed, on_tpu):
    """``unsort``: a sort keyed on the permutation where the program is
    placed on a TPU, ONE packed scatter elsewhere; the same columns."""
    placed(on_tpu)
    rng = np.random.default_rng(11)
    n = 700
    order = jnp.asarray(rng.permutation(n), jnp.int32)
    cols = tuple(jnp.asarray(rng.integers(-9, 9, n), jnp.int32)
                 for _ in range(2))
    same(edges.unsort(order, cols),
         tuple(jnp.zeros(n, jnp.int32).at[order].set(c) for c in cols))
    # a fresh function: a trace is kept by the function's identity
    text = str(jax.make_jaxpr(lambda o, c: edges.unsort(o, c))(order, cols))
    assert ("scatter[" in text) != on_tpu and (" sort[" in text) == on_tpu


@pytest.mark.parametrize("packed", PACKED)
def test_sort_pairs_is_the_old_sort_pairs(packed):
    rng = np.random.default_rng(7)
    n, capP = 900, 30
    a = jnp.asarray(rng.integers(0, capP, n), jnp.int32)
    b = jnp.asarray(rng.integers(0, capP, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    tag = jnp.asarray(rng.integers(0, 99, n), jnp.uint32)
    *got, (tag_s,) = edges.sort_pairs(
        a, b, valid, capP if packed else edges.PACK_LIMIT + 1, (tag,))
    ref = old_sort_pairs(a, b, valid, capP, packed)
    same(got, ref)
    same(tag_s, tag[ref[0]])


# ---- the edge table ---------------------------------------------------------

@pytest.mark.parametrize("shell_slots", [0, 3])
@pytest.mark.parametrize("packed", PACKED)
@pytest.mark.parametrize("on_tpu", ON_TPU)
def test_unique_edges_is_the_old_table(placed, branch, on_tpu, packed,
                                       shell_slots):
    placed(on_tpu)
    branch(packed)
    m = awkward_mesh()
    same(edges.unique_edges(m, shell_slots),
         old_unique_edges(m, shell_slots, packed))


@pytest.mark.parametrize("on_tpu", ON_TPU)
def test_a_table_from_a_given_sort_is_the_fresh_table(placed, on_tpu):
    """``unique_edges_from_sorted`` (the merged sorts of ops/topo_incr
    carry no tags: the epilogue fetches them) equals ``unique_edges``."""
    placed(on_tpu)
    m = awkward_mesh()
    n6 = m.capT * 6
    ev = tet_edge_vertices(m.tet).reshape(n6, 2)
    key = jnp.where(jnp.repeat(m.tmask, 6),
                    jnp.minimum(ev[:, 0], ev[:, 1]) * m.capP
                    + jnp.maximum(ev[:, 0], ev[:, 1]), I32_MAX)
    order = jnp.argsort(key)
    same(edges.unique_edges_from_sorted(m, order, key[order], 3),
         edges.unique_edges(m, 3))


# ---- the faces --------------------------------------------------------------

def old_twins(mesh, packed):
    t, f, partner, matched, valid_s = old_face_sort(mesh, packed)
    return t, f, t[partner], f[partner], matched, valid_s


@pytest.mark.parametrize("packed", PACKED)
def test_face_sort_is_the_old_sort_and_its_twins(branch, packed):
    branch(packed)
    m = awkward_mesh()
    got = adj.face_sort(m)
    same(got, old_twins(m, packed))
    # the mesh has what the test is for: twins, lone faces, and a face
    # of three tets, of which the sort pairs the first two slots
    matched = np.asarray(got[4])
    assert matched.any() and (~matched & np.asarray(got[5])).any()
    cols = np.asarray(adj._face_keys(m))
    _, counts = np.unique(cols[cols[:, 0] != I32_MAX], axis=0,
                          return_counts=True)
    assert counts.max() == 3


def test_a_twin_is_the_slot_a_partner_index_would_fetch():
    rng = np.random.default_rng(9)
    n = 700
    eq_next = jnp.asarray(rng.random(n - 1) < 0.5)
    same_next = jnp.concatenate([eq_next, jnp.array([False])])
    same_prev = jnp.concatenate([jnp.array([False]), eq_next])
    idx = jnp.arange(n)
    partner = jnp.where(same_next, idx + 1,
                        jnp.where(same_prev, idx - 1, idx))
    x = jnp.asarray(rng.integers(0, 1000, n), jnp.int32)
    same(adj.twin(x, same_next, same_prev), x[partner])


@pytest.mark.parametrize("packed", PACKED)
@pytest.mark.parametrize("on_tpu", ON_TPU)
def test_build_adjacency_is_the_old_adjacency(placed, branch, on_tpu, packed):
    placed(on_tpu)
    branch(packed)
    m = awkward_mesh()
    same(adj.build_adjacency(m), old_build_adjacency(m, packed))


def test_records_from_a_given_sort_are_the_fresh_records():
    m = awkward_mesh()
    cols = adj._face_keys(m)
    w = adj.pack_minor(cols, m.capP)
    order = jnp.lexsort((w, cols[:, 0]))
    same(adj.face_records_from_sorted(order, cols[order, 0], w[order]),
         adj.face_sort(m))


@pytest.mark.parametrize("packed", PACKED)
def test_pair_fields_facesort_is_the_old_pairing(branch, packed):
    branch(packed)
    m = awkward_mesh()
    q_tet = jnp.asarray(np.random.default_rng(1).random(m.capT),
                        jnp.float32)
    same(swap._pair_fields_facesort(m, q_tet, m.capT),
         old_pair_fields_facesort(m, q_tet, m.capT, packed))


# ---- the collapse's tag joins -----------------------------------------------

def a_collapse(m, seed=2):
    """A remap as a collapse wave leaves it: a tenth of the live tets
    die, one vertex of each onto another."""
    rng = np.random.default_rng(seed)
    tet = np.asarray(m.tet).copy()
    tmask = np.asarray(m.tmask)
    dead = np.zeros(m.capT, bool)
    dead[rng.choice(np.flatnonzero(tmask), tmask.sum() // 10,
                    replace=False)] = True
    remap = np.arange(m.capP)
    for t in np.flatnonzero(dead):
        remap[tet[t, 0]] = tet[t, 1]
    return jnp.asarray(remap[tet], jnp.int32), jnp.asarray(dead), \
        jnp.asarray(tmask & ~dead)


@pytest.mark.parametrize("packed", PACKED)
def test_tag_joins_core_is_the_old_join(branch, packed):
    branch(packed)
    m = awkward_mesh()
    new_tet, dead, tmask = a_collapse(m)
    got = collapse._tag_joins_core(new_tet, m.ftag, m.fref, m.etag, dead,
                                   tmask, m.capP)
    ref = old_tag_joins_core(new_tet, m.ftag, m.fref, m.etag, dead, tmask,
                             m.capP, packed)
    same(got, ref)
    assert all(np.asarray(a).any() for a in ref)   # something was handed on


@pytest.mark.parametrize("band", ["1", "0"])
def test_collapse_tag_joins_is_the_old_join_merged(monkeypatch, band):
    """The banded join and the full-width one, against the old join at
    full width merged as ``_collapse_tag_joins`` merges it."""
    monkeypatch.setenv("PARMMG_COLLAPSE_BAND", band)
    vert, tet = cube_mesh(5)
    big = make_mesh(vert, tet, capP=2 * len(vert), capT=2 * len(tet))
    rng = np.random.default_rng(4)
    bits = np.array([0, MG_BDY, MG_REF, MG_GEO | MG_REQ], np.uint32)
    m = dataclasses.replace(
        big, etag=jnp.asarray(bits[rng.integers(0, 4, (big.capT, 6))]),
        ftag=jnp.asarray(bits[rng.integers(0, 4, (big.capT, 4))]),
        fref=jnp.asarray(rng.integers(0, 5, (big.capT, 4)), jnp.int32))
    new_tet, dead, tmask = a_collapse(m)
    dead = dead & (jnp.arange(m.capT) < 40)     # a wave's few: the band holds
    tmask = m.tmask & ~dead
    assert collapse.collapse_band_width(m.capT) < m.capT
    got = collapse._collapse_tag_joins(m, new_tet, dead, tmask, m.capT,
                                       m.capP)
    at, ar, ae = old_tag_joins_core(new_tet, m.ftag, m.fref, m.etag, dead,
                                    tmask, m.capP, True)
    ref = (jnp.where(tmask[:, None], m.ftag | at, m.ftag),
           jnp.where(tmask[:, None] & (m.fref == 0) & (ar != 0), ar,
                     m.fref),
           jnp.where(tmask[:, None], m.etag | ae, m.etag))
    same(got, ref)
    assert (np.asarray(got[0]) != np.asarray(m.ftag)).any()


# ---- two cycles of a block, traced as on a TPU, against the parent's --------

@pytest.mark.parametrize("cell", ["iso-growth", "aniso-coarsen"])
def test_a_block_traced_as_on_a_tpu_equals_the_parents(monkeypatch, cell):
    """The block program with its payloads carried in the sorts, its
    swap23 paired off the face sort and its surface scatters listed (what
    ``placed_on_tpu`` turns on) hands back the arrays the parent's
    full-width program did: ``tests/test_rowpack.py``'s hashes (the edge
    tags apart, as there), with the one column of the counts row that
    says the lists engaged (7, ``listed``: 0 at full width) left out."""
    import test_rowpack
    digest = test_rowpack.digest

    def unlisted(stacked, met_s, counts):
        counts = [c.copy() for c in counts]
        for c in counts:
            assert c[7] > 0
            c[7] = 0
        return digest(stacked, met_s, counts)
    monkeypatch.setattr(test_rowpack, "digest", unlisted)
    monkeypatch.setattr(placement, "placed_on_tpu", lambda: True)
    monkeypatch.setattr(groups, "placed_on_tpu", lambda: True)
    monkeypatch.setattr(groups, "_GROUP_BLOCK_CACHE", {})
    got, etag = test_rowpack.two_cycles(cell)
    assert got == test_rowpack.PARENT[cell]
    assert etag == test_rowpack.ETAG[cell]
