"""Tet-tet adjacency and boundary detection, sort-based (jittable).

Replaces the reference's hash-table face matching (``MMG3D_hashTetra``, used
at e.g. /root/reference/src/libparmmg1.c:733, and the parallel edge hashes of
hash_pmmg.c:147-234) with the TPU idiom: materialize all 4*capT faces as
sorted vertex triples, sort them, and match equal neighbors in sorted order.
Sorting is XLA-friendly (static shapes, no data-dependent control flow); a
hash table with chaining is not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh, tet_face_vertices
from ..core.constants import MG_BDY
from ..obs import trace as otrace


def _face_keys(mesh: Mesh):
    """Sorted-triple face keys as 3 int32 columns, invalid tets last.

    Pure int32 (no int64 emulation on TPU): multi-column keys are matched
    with a multi-column sort + column-wise equality instead of one packed
    key.  Returns cols [F,3]; slot i is face i % 4 of tet i // 4.
    """
    capT = mesh.capT
    fv = tet_face_vertices(mesh.tet).reshape(capT * 4, 3)       # [F,3]
    fv = jnp.sort(fv, axis=1)
    invalid = ~jnp.repeat(mesh.tmask, 4)
    big = jnp.iinfo(jnp.int32).max
    return jnp.where(invalid[:, None], big, fv)


def face_sort(mesh: Mesh):
    """THE face-sort pass, shared by ``build_adjacency`` and the direct
    swap23 pairing (``ops.swap.swap23_wave(..., facesort=True)``).

    Returns sorted-order face records ``(t, f, tp, fp, matched,
    valid_s)``: per sorted slot the tet id and local face id, those of
    the twin slot (its own if unmatched), whether a twin exists, and
    whether the slot belongs to a live tet.  Matched twins are adjacent
    in sorted order, so ``(t[i], f[i]) <-> (tp[i], fp[i])`` IS the
    face-pair table — consumers that only need the pairing (swap23
    candidate selection) read it here without materializing the [capT,4]
    ``adja`` matrix.  The sort hands back its own sorted key columns
    (``edges.sort_carry``) and a twin is read by a shift: nothing is
    fetched through the permutation.  The sort itself is cheap on the
    chip (PERF.md section 5: a block's sorts are 2 % of it).
    """
    from .edges import PACK_LIMIT, sort_carry
    with otrace.scope("tab.adjacency"):
        cols = _face_keys(mesh)
        if mesh.capP <= PACK_LIMIT:
            # pack the two minor columns into one int32 (ids < capP <=
            # sqrt(2^31)): two key columns instead of three
            order, (k0, kw), _ = sort_carry(
                (cols[:, 0], pack_minor(cols, mesh.capP)))
            return face_records_from_sorted(order, k0, kw)
        order, k, _ = sort_carry((cols[:, 0], cols[:, 1], cols[:, 2]))
        return _pair_records(k, order)


def pack_minor(cols, capP: int):
    """The two minor columns of ``_face_keys`` packed into one int32
    (INT32_MAX on invalid slots); needs ``capP <= PACK_LIMIT``."""
    big = jnp.iinfo(jnp.int32).max
    return jnp.where(cols[:, 0] == big, big, cols[:, 1] * capP + cols[:, 2])


def face_records_from_sorted(order: jax.Array,
                             k0: jax.Array, kw: jax.Array):
    """``face_sort``'s record tuple from a precomputed PACKED face sort:
    ``order`` is the stable sort permutation over the 4*capT face slots,
    ``k0``/``kw`` the ascending (major vertex, packed minor pair) key
    columns — exactly what the packed sort produces.  Factored so the
    incremental path (ops/topo_incr) feeds its band-merged sort through
    the SAME twin-pairing epilogue.  ``t = order // 4`` / ``f = order %
    4``: the slot layout is tet-major.  Requires ``capP <=
    PACK_LIMIT``."""
    return _pair_records((k0, kw), order.astype(jnp.int32))


def twin(x, same_next, same_prev):
    """``x`` of each sorted slot's twin, its own where it has none: the
    twin is the NEXT slot where ``same_next`` and the previous one where
    ``same_prev``, so it is read by a shift, not fetched by index."""
    up = jnp.concatenate([x[1:], x[-1:]])
    dn = jnp.concatenate([x[:1], x[:-1]])
    return jnp.where(same_next, up, jnp.where(same_prev, dn, x))


def _pair_records(k, order):
    """Twin pairing over sorted face key columns ``k`` (shared epilogue):
    matched twins are adjacent in sorted order.  A slot is face
    ``order % 4`` of tet ``order // 4``, its twin's the same of the
    twin's slot."""
    from .edges import segment_first
    big = jnp.iinfo(jnp.int32).max
    first = segment_first(k)
    eq_next = ~first[1:] & (k[0][:-1] != big)
    same_next = jnp.concatenate([eq_next, jnp.array([False])])
    same_prev = jnp.concatenate([jnp.array([False]), eq_next])
    other = twin(order, same_next, same_prev)
    return (order // 4, order % 4, other // 4, other % 4,
            same_next | same_prev, k[0] != big)


def bdy_tags_from_sort(mesh: Mesh, t, f, matched, valid_s):
    """The MG_BDY face tagging of ``build_adjacency`` computed straight
    off the face-sort records: a live unmatched slot IS a boundary face
    (``adja < 0 & tmask`` of the adja path, by construction — adja is -1
    exactly on unmatched live slots and dead rows).  One permutation
    scatter replaces the adja materialization + compare."""
    unb = valid_s & ~matched
    hit = jnp.zeros((mesh.capT, 4), bool).at[t, f].set(
        unb, unique_indices=True)
    ftag = jnp.where(hit, mesh.ftag | MG_BDY, mesh.ftag)
    return dataclasses_replace(mesh, ftag=ftag)


def build_adjacency(mesh: Mesh) -> Mesh:
    """Compute ``adja`` and mark unmatched faces as boundary (MG_BDY).

    In a conforming mesh every interior face appears exactly twice. After
    sorting face keys, twins are neighbors in sorted order; the pairing is
    put back in slot order as ``adja[t,f] = 4*t' + f'``.
    """
    with otrace.scope("tab.adjacency"):
        t, f, tp, fp, matched, _ = face_sort(mesh)
        return adjacency_from_records(mesh, t, f, tp, fp, matched)


def adjacency_from_records(mesh: Mesh, t, f, tp, fp, matched) -> Mesh:
    """``build_adjacency``'s epilogue from face-sort records (the twins
    back in slot order, ``edges.unsort``) — shared with the incremental
    path (ops/topo_incr), which feeds it band-merged records."""
    from .edges import unsort
    capT = mesh.capT
    adj_val = jnp.where(matched, 4 * tp + fp, -1)

    # slot 4 * t + f runs over a permutation of all slots
    (adja,) = unsort(4 * t + f, (adj_val.astype(jnp.int32),))
    adja = jnp.where(mesh.tmask[:, None], adja.reshape(capT, 4), -1)

    # boundary faces: valid tet, face has no twin
    is_bdy = (adja < 0) & mesh.tmask[:, None]
    ftag = jnp.where(is_bdy, mesh.ftag | MG_BDY, mesh.ftag)
    return dataclasses_replace(mesh, adja=adja, ftag=ftag)


def dataclasses_replace(mesh: Mesh, **kw) -> Mesh:
    import dataclasses
    return dataclasses.replace(mesh, **kw)


def check_adjacency(mesh: Mesh) -> dict:
    """Invariant oracle (debug): symmetric adja, shared vertices agree.

    The analogue of the reference's communicator/adjacency assertions
    (chkcomm_pmmg.c): run off the hot path, returns violation counts.
    """
    adja = mesh.adja
    nb = adja >> 2
    nf = adja & 3
    valid = adja >= 0
    # symmetry: adja[nb, nf] must point back
    back = jnp.where(valid, adja[jnp.clip(nb, 0, mesh.capT - 1), nf], -1)
    tid = jnp.arange(mesh.capT, dtype=jnp.int32)[:, None]
    fid = jnp.arange(4, dtype=jnp.int32)[None, :]
    sym_bad = jnp.sum(jnp.where(valid, back != 4 * tid + fid, False))
    # shared face must consist of the same 3 vertices
    fv = jnp.sort(tet_face_vertices(mesh.tet), axis=2)           # [T,4,3]
    nbv = fv[jnp.clip(nb, 0, mesh.capT - 1), nf]
    face_bad = jnp.sum(
        jnp.where(valid[..., None], fv != nbv, False))
    return {"asymmetric": int(sym_bad), "face_mismatch": int(face_bad)}


def boundary_edge_tags(mesh: Mesh, lists=None) -> Mesh:
    """Propagate MG_BDY from boundary faces to their edges and vertices.

    ``lists``: an ``ops/surflist.Tally`` (default: one that observes
    where the program is placed); where it is on, the vertex scatter
    runs over the listed boundary faces alone."""
    from ..core.constants import FACE_EDGES
    from . import surflist
    lists = surflist.Tally() if lists is None else lists
    fe = jnp.asarray(FACE_EDGES)                     # [4,3]
    is_bdy_face = (mesh.ftag & MG_BDY) != 0          # [T,4]
    # edges of boundary faces get MG_BDY
    etag = mesh.etag
    edge_hit = jnp.zeros((mesh.capT, 6), bool)
    for f in range(4):
        for j in range(3):
            e = int(FACE_EDGES[f, j])
            edge_hit = edge_hit.at[:, e].set(edge_hit[:, e] | is_bdy_face[:, f])
    etag = jnp.where(edge_hit, etag | MG_BDY, etag)
    # vertices of boundary faces get MG_BDY — ONE concatenated scatter
    # over all 4 faces (per-op overhead dominates scatter cost on this
    # device; 4 narrow scatters cost ~4x one long one)
    from ..core.constants import IDIR
    vtag = mesh.vtag
    capP = mesh.capP
    if lists.on:
        # a face's three vertices go together: a max takes any order
        live = surflist.Live(jnp.concatenate(
            [is_bdy_face[:, f] & mesh.tmask for f in range(4)]))
        lists.note(3 * live.count)

        def updates(p, ok):
            fv = surflist.face_vertices(mesh.tet[p % mesh.capT],
                                        p // mesh.capT)
            idx = jnp.where(ok[:, None], fv, capP).reshape(-1)
            return idx, jnp.ones(idx.shape, bool)
        hit = surflist.staged_scatter(jnp.zeros(capP + 1, bool), live,
                                      updates, op="max")
    else:
        vids_all = jnp.concatenate(
            [mesh.tet[:, jnp.asarray(IDIR[f])].reshape(-1)
             for f in range(4)])
        m_all = jnp.concatenate(
            [jnp.repeat(is_bdy_face[:, f] & mesh.tmask, 3)
             for f in range(4)])
        hit = jnp.zeros(capP + 1, bool).at[
            jnp.where(m_all, vids_all, capP)].max(m_all, mode="drop")
    vtag = jnp.where(hit[:capP], vtag | MG_BDY, vtag)
    return dataclasses_replace(mesh, etag=etag, vtag=vtag)
