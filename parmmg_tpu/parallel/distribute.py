"""Distribute a mesh into shards and merge shards back (host orchestration).

Reference analogues: ``PMMG_distribute_mesh`` (distributemesh_pmmg.c:1109)
splits the rank-0 mesh along a partition and sends each piece to its rank;
``PMMG_merge_parmesh`` (mergemesh_pmmg.c:1571) gathers everything back and
dedups interface entities through the node communicators.  Here shards are
slots of a stacked pytree (leading device axis) and interface vertices are
deduplicated at merge time by *exact* coordinate match — sound because
parallel-interface points are frozen (``MG_PARBDY | MG_REQ``; reference
tag contract tag_pmmg.c:39-124) and thus bit-identical on all shards.

The interface tagging applied here IS the freeze contract: interface faces
get MG_PARBDY|MG_BDY|MG_REQ|MG_NOSURF, their edges and vertices likewise
(+ MG_PARBDYBDY on entities that are also true boundary), so the shard-local
adapt operator (ops/adapt.py) leaves the interface untouched.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import jax
import jax.numpy as jnp

from ..core.mesh import Mesh, make_mesh, mesh_to_host
from ..core.constants import (
    IDIR, FACE_EDGES, IARE, MG_BDY, MG_PARBDY, MG_PARBDYBDY, MG_REQ,
    MG_NOSURF, PARBDY_TAGS)
from ..ops.adjacency import build_adjacency, boundary_edge_tags


# room to grow a shard must still have inside a capacity that is kept
# from an earlier split (shard_capacity ``keep``)
REUSE_SLACK = 1.25


def shard_capacity(maxP: int, maxT: int, cap_mult: float = 3.0,
                   keep: tuple | None = None) -> tuple[int, int]:
    """(capP, capT) for shards whose largest holds ``maxP`` vertices and
    ``maxT`` tets — the ONE capacity rule of the split (compile
    governor): every per-shard and per-group program (adapt blocks,
    flood, migration, analysis) keys its compile on (capP, capT), and a
    compile of the cycle block costs minutes on a TPU (PERF.md, PR 26).

    Fresh: ``cap_mult`` times the largest shard, rounded up the
    geometric 1.5x ladder of ``compilecache.bucket`` — exact sizes
    drift with every re-split, the ladder bounds the overshoot while
    collapsing them onto O(log n) shapes.  ``keep``: the capacity an
    earlier split of this run compiled its programs for; it stands
    while every shard still fits in it with REUSE_SLACK to grow (a
    later pass splits a mesh that is already near its metric), and a
    column that no longer fits goes to the lowest rung that does hold
    its largest shard with that slack, the next one as a rule -- not
    to ``cap_mult`` times a shard that a displacement filled (three
    rungs up, a program nobody compiled: ROADMAP B11).  An overflow
    regrows either way (``regrown_capacity``)."""
    from ..utils.compilecache import bucket
    if keep is not None:
        return tuple(max(kept, bucket(math.ceil(REUSE_SLACK * n),
                                      floor=64, scheme="geo"))
                     for kept, n in zip(keep, (maxP, maxT)))
    return (bucket(int(cap_mult * maxP), floor=64, scheme="geo"),
            bucket(int(cap_mult * maxT), floor=64, scheme="geo"))


def capacity_headroom(maxP: int, maxT: int, capP: int, capT: int) -> float:
    """Per cent of (capP, capT) between shards whose largest holds
    ``maxP`` vertices and ``maxT`` tets and the edge where
    ``shard_capacity`` stops keeping that capacity, the lesser of the
    two columns: at 0 the largest shard just fits with REUSE_SLACK,
    under it a later split takes the next rung."""
    return 100.0 * (1.0 - REUSE_SLACK * max(maxP / capP, maxT / capT))


def regrown_capacity(capP: int, capT: int) -> tuple[int, int]:
    """Capacity after an overflow: the ladder rung at or above twice
    the old one, so a regrown pass and a later fresh split meet on the
    same shapes and share their compiled programs."""
    from ..utils.compilecache import bucket
    return (bucket(2 * capP, floor=64, scheme="geo"),
            bucket(2 * capT, floor=64, scheme="geo"))


def _fan_normals(vert, tet, ftag, want):
    """[len(vert), 3] float64: the unit surface normal of each vertex in
    ``want`` [len(vert)] bool from ALL its true-boundary faces, zero
    elsewhere.  ``ops.analysis.boundary_vertex_normals`` in numpy (the
    same corner weights), over the few faces that touch ``want``: the
    split's arrays are on the host, and a device program at the merged
    mesh's width would compile anew for every width a pass ends at."""
    from ..core.constants import EPSD
    acc = np.zeros((len(vert), 3))
    for f in range(4):
        tri = tet[((ftag[:, f] & MG_BDY) != 0)
                  & ((ftag[:, f] & MG_PARBDY) == 0)][:, IDIR[f]]
        tri = tri[want[tri].any(axis=1)]
        p = vert[tri].astype(np.float64)
        fn = np.cross(p[:, 1] - p[:, 0], p[:, 2] - p[:, 0])
        l2 = [((p[:, (k + 1) % 3] - p[:, k]) ** 2).sum(-1) for k in range(3)]
        for k in range(3):
            wgt = 1.0 / np.maximum(l2[k] * l2[(k + 2) % 3], EPSD)
            np.add.at(acc, tri[:, k], fn * wgt[:, None])
    acc[~want] = 0.0
    return acc / np.maximum(np.linalg.norm(acc, axis=1, keepdims=True), EPSD)


def split_to_shards(mesh: Mesh, met, part: np.ndarray, nparts: int,
                    cap_mult: float = 3.0, return_l2g: bool = False,
                    reuse_caps: tuple | None = None,
                    cut: dict | None = None):
    """Split a host-resident Mesh into ``nparts`` shard Meshes (stacked).

    Returns (shards: Mesh with leading axis [nparts, ...], met stacked),
    plus the per-shard local->global vertex maps when ``return_l2g`` (the
    input to build_interface_comms).  All shards share one capacity (max
    over shards * cap_mult / nparts-balance) so they stack into one
    pytree for shard_map.

    ``cut``: a dict the caller hands in to learn what the cut asks of the
    pass that runs it, from what the split computes on its way (the face
    pairs, each part's vertices): ``verts`` the vertices of any tet,
    ``seam_verts`` those of two or more parts (the pass freezes them),
    ``junction_verts`` those of three or more (where seams meet),
    ``pieces`` the face-connected pieces summed over the parts
    (``nparts`` when every part is in one), ``maxP`` / ``maxT`` the
    vertices and tets of the fullest part, which the capacity follows.
    Only the pieces are labelled for it (``partition.cut_pieces``).
    """
    vert, tet, vref, tref, vtag = mesh_to_host(mesh)
    methost = np.asarray(met)
    vm = np.asarray(mesh.vmask)
    tm = np.asarray(mesh.tmask)
    new_id = np.cumsum(vm) - 1
    methost = methost[vm]
    # per-tet face/edge tags + refs travel with the tets: ridge (MG_GEO)
    # and reference data must survive the split — the waves rely on edge
    # tags for the freeze contract, and fref is user data (the reference
    # ships whole MMG5_xTetra records in the group pack,
    # mpipack_pmmg.c:~400; dropping them here silently eroded ridges in
    # the distributed path)
    ftag_h = np.asarray(mesh.ftag)[tm]
    fref_h = np.asarray(mesh.fref)[tm]
    etag_h = np.asarray(mesh.etag)[tm]
    part = np.asarray(part, np.int32)
    assert part.shape[0] == len(tet)

    # interface faces: faces shared by tets of different parts
    n = len(tet)
    faces = np.sort(tet[:, IDIR].reshape(n * 4, 3), axis=1)
    key = (faces[:, 0].astype(np.int64) << 42) | \
          (faces[:, 1].astype(np.int64) << 21) | faces[:, 2].astype(np.int64)
    order = np.argsort(key, kind="stable")
    ks = key[order]
    same = ks[1:] == ks[:-1]
    fA = order[:-1][same]
    fB = order[1:][same]
    cross = part[fA // 4] != part[fB // 4]
    ifc_faces = np.concatenate([fA[cross], fB[cross]])   # global face slots

    # mark interface vertices
    ifc_vert = np.zeros(len(vert), bool)
    ifc_vert[faces[ifc_faces].reshape(-1)] = True

    shards_m = []
    shards_met = []
    maxP = maxT = 0
    locals_ = []
    nof = np.zeros(len(vert), np.int32)      # parts a vertex is in
    for p in range(nparts):
        sel = part == p
        ltet_g = tet[sel]
        used = np.zeros(len(vert), bool)
        used[ltet_g.reshape(-1)] = True
        nof += used
        g2l = np.full(len(vert), -1, np.int64)
        gids = np.where(used)[0]
        g2l[gids] = np.arange(len(gids))
        locals_.append((gids, ltet_g, np.where(sel)[0]))
        maxP = max(maxP, len(gids))
        maxT = max(maxT, len(ltet_g))

    capP, capT = shard_capacity(maxP, maxT, cap_mult, keep=reuse_caps)
    if cut is not None:
        from .partition import cut_pieces
        # host numpy throughout: count_nonzero and tolist give python ints
        cut.update(
            verts=np.count_nonzero(nof),
            seam_verts=np.count_nonzero(nof >= 2),
            junction_verts=np.count_nonzero(nof >= 3),
            pieces=(cut_pieces(tet, part, (fA // 4, fB // 4)).max()
                    + 1).tolist(),
            maxP=maxP, maxT=maxT)

    face_is_ifc = np.zeros(n * 4, bool)
    face_is_ifc[ifc_faces] = True
    face_is_ifc = face_is_ifc.reshape(n, 4)
    # seam edges, the edges of the interface faces, as packed vertex
    # pairs: EVERY slot of one is frozen, in every tet of its shell (see
    # where the shards' edge tags are set)
    nv = np.int64(len(vert))
    tri = faces[ifc_faces].astype(np.int64)
    seam_edges = np.unique(np.concatenate(
        [tri[:, i] * nv + tri[:, j] for i, j in ((0, 1), (0, 2), (1, 2))]))

    # a regular surface vertex on a seam keeps the normal of its WHOLE
    # fan (Mmg's xPoint n1, which the reference agrees on across ranks,
    # analys_pmmg.c:199-1171): inside its shard the seam cuts the fan
    # and the near side's faces alone give a tilted one, which every
    # Bezier lift from that vertex would follow.  Feature points have
    # no single normal and carry none
    from ..core.constants import MG_CRN, MG_GEO, MG_NOM, MG_REF
    carry = ifc_vert & ((vtag & MG_BDY) != 0) & \
        ((vtag & (MG_GEO | MG_CRN | MG_NOM | MG_REF)) == 0)
    vn_h = _fan_normals(vert, tet, ftag_h, carry)

    for p in range(nparts):
        gids, ltet_g, tsel = locals_[p]
        g2l = np.full(len(vert), -1, np.int64)
        g2l[gids] = np.arange(len(gids))
        lvert = vert[gids]
        ltet = g2l[ltet_g].astype(np.int32)
        sm = make_mesh(lvert, ltet, vref=vref[gids], tref=tref[tsel],
                       capP=capP, capT=capT, dtype=mesh.dtype)
        # carry original tags
        svtag = np.zeros(capP, np.uint32)
        svtag[: len(gids)] = vtag[gids]
        # freeze interface: vertices.  MG_NOSURF marks REQ as OURS — a
        # vertex the user already required must NOT carry NOSURF, or the
        # merge would strip the user's REQ along with the freeze
        # (tag_pmmg.c NOSURF semantics: "REQ set by us, can be relaxed")
        on_ifc = ifc_vert[gids]
        user_req_v = (svtag[: len(gids)] & MG_REQ) != 0
        svtag[: len(gids)][on_ifc] |= PARBDY_TAGS
        svtag[: len(gids)][on_ifc & user_req_v] &= ~np.uint32(MG_NOSURF)
        # PARBDYBDY: interface vertex that is also true boundary
        true_bdy = (vtag[gids] & MG_BDY) != 0
        svtag[: len(gids)][on_ifc & true_bdy] |= MG_PARBDYBDY
        # faces + edges: carry the global tags/refs, then freeze interface
        sftag = np.zeros((capT, 4), np.uint32)
        setag = np.zeros((capT, 6), np.uint32)
        sfref = np.zeros((capT, 4), np.int32)
        sftag[: len(ltet)] = ftag_h[tsel]
        setag[: len(ltet)] = etag_h[tsel]
        sfref[: len(ltet)] = fref_h[tsel]
        lf_ifc = face_is_ifc[tsel]                       # [nt,4]
        user_req_f = (sftag[: len(ltet)] & MG_REQ) != 0
        sftag[: len(ltet)][lf_ifc] |= PARBDY_TAGS
        sftag[: len(ltet)][lf_ifc & user_req_f] &= ~np.uint32(MG_NOSURF)
        # a seam edge is frozen in EVERY slot of its shell, not only in
        # the tets that own one of its seam faces: a swap routes a new
        # tet's edge tag from ONE old slot of that edge, and where that
        # slot was a bare one of a tet beside the seam, a tet that came to
        # own the seam face carried the edge unfrozen, the split took it,
        # and the seam's two sides no longer matched (ROADMAP B1, B17)
        ea, eb = ltet_g[:, IARE[:, 0]], ltet_g[:, IARE[:, 1]]
        e_ifc_m = np.isin(np.minimum(ea, eb).astype(np.int64) * nv
                          + np.maximum(ea, eb), seam_edges)
        # an interface edge that was ALSO true boundary keeps that fact
        # through the freeze via MG_PARBDYBDY (tag_pmmg.c PARBDYBDY
        # role); a user-required edge keeps REQ by NOT carrying NOSURF
        pre_bdy_e = (setag[: len(ltet)] & MG_BDY) != 0
        user_req_e = (setag[: len(ltet)] & MG_REQ) != 0
        setag[: len(ltet)][e_ifc_m] |= PARBDY_TAGS
        setag[: len(ltet)][e_ifc_m & pre_bdy_e] |= MG_PARBDYBDY
        setag[: len(ltet)][e_ifc_m & user_req_e] &= ~np.uint32(MG_NOSURF)
        svnrm = np.zeros((capP, 3))
        svnrm[: len(gids)] = vn_h[gids]
        sm = dataclasses.replace(
            sm, vtag=jnp.asarray(svtag),
            vnrm=jnp.asarray(svnrm, mesh.dtype),
            ftag=jnp.maximum(sm.ftag, jnp.asarray(sftag)),
            etag=jnp.maximum(sm.etag, jnp.asarray(setag)),
            fref=jnp.asarray(sfref))
        sm = boundary_edge_tags(build_adjacency(sm))
        shards_m.append(sm)
        lmet = np.zeros((capP,) + methost.shape[1:], methost.dtype)
        lmet[: len(gids)] = methost[gids]
        shards_met.append(jnp.asarray(lmet))

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *shards_m)
    met_stacked = jnp.stack(shards_met)
    if return_l2g:
        return stacked, met_stacked, [loc[0] for loc in locals_]
    return stacked, met_stacked


def _weld_close_pairs(vert, tet, vtag, met, tref, ftag, etag,
                      tol_rel: float = 0.1):
    """Contract near-coincident untagged vertex pairs, sequentially.

    Independent refinement on the two sides of a frozen interface can
    drop interior points a tiny distance apart (each shard splits its own
    near-mirror edges); after the merge these tangled clusters deadlock
    the batched collapse wave (any single contraction inverts a sliver
    spanning the gap, so every direction is vetoed in parallel — while a
    SEQUENTIAL pass resolves the chain pair by pair, trying both
    directions, exactly like the reference's one-op-at-a-time remesher
    would).  Host-side, O(pairs); pairs are vertices closer than
    ``tol_rel`` x their metric size with BOTH tags clear, welded only
    when every rewritten tet stays positive and every dying tet is
    untagged.

    Returns (tet, vkeep, tkeep) — updated connectivity plus vertex/tet
    keep masks.
    """
    # the host repair's Euclidean quality (numpy, 1 on the regular tet)
    from ..ops.repair import _qual as tet_quality
    n = len(vert)
    if met is None:
        return tet, np.ones(n, bool), np.ones(len(tet), bool)
    if met.ndim == 1:
        href = met
    else:  # aniso: isotropic proxy h ~ 1/sqrt(mean diagonal eigenvalue)
        diag = (met[:, 0] + met[:, 3] + met[:, 5]) / 3.0
        href = 1.0 / np.sqrt(np.maximum(diag, 1e-30))
    free = vtag == 0
    if not free.any():
        return tet, np.ones(n, bool), np.ones(len(tet), bool)
    # vectorized prefilter: any pair within the weld radius collides in
    # at least one of the 8 half-cell-shifted grids at cell = 2*radius —
    # O(n log n) numpy, no Python loops on the (typical) no-pair path
    cell = max(1e-12, 2.0 * float(np.median(tol_rel * href[free])))
    fidx = np.where(free)[0]
    fv = vert[fidx]
    sus = np.zeros(len(fidx), bool)
    for sx in (0.0, 0.5):
        for sy in (0.0, 0.5):
            for sz in (0.0, 0.5):
                k = np.floor(fv / cell +
                             np.array([sx, sy, sz])).astype(np.int64)
                kk = (k[:, 0] << 42) ^ (k[:, 1] << 21) ^ k[:, 2]
                _, inv, cnts = np.unique(kk, return_inverse=True,
                                         return_counts=True)
                sus |= cnts[inv] > 1
    cand_v = fidx[sus]
    if not len(cand_v):
        return tet, np.ones(n, bool), np.ones(len(tet), bool)
    import collections
    import itertools
    key = np.round(vert / cell).astype(np.int64)
    cells = collections.defaultdict(list)
    for i in cand_v:
        cells[tuple(key[i])].append(int(i))
    cand_pairs = []
    for k, lst in cells.items():
        for dx in itertools.product((-1, 0, 1), repeat=3):
            k2 = (k[0] + dx[0], k[1] + dx[1], k[2] + dx[2])
            other = cells.get(k2)
            if not other:
                continue
            for i in lst:
                for j in other:
                    if i < j:
                        d = np.linalg.norm(vert[i] - vert[j])
                        if d < tol_rel * min(href[i], href[j]):
                            cand_pairs.append((d, i, j))
    if not cand_pairs:
        return tet, np.ones(n, bool), np.ones(len(tet), bool)
    cand_pairs.sort()
    # vertex -> tets incidence, restricted to tets touching a candidate
    touch = np.isin(tet, cand_v).any(axis=1)
    inc = collections.defaultdict(set)
    for t_i in np.where(touch)[0]:
        for v in tet[t_i]:
            inc[int(v)].add(int(t_i))
    tet = tet.copy()
    tkeep = np.ones(len(tet), bool)
    vkeep = np.ones(n, bool)

    def try_weld(rm, kp):
        ball = [t_i for t_i in inc[rm] if tkeep[t_i]]
        dying, moved = [], []
        for t_i in ball:
            row = tet[t_i]
            if kp in row:
                # must carry no tags to die silently, and a weld must
                # not bridge different regions
                if ftag[t_i].any() or etag[t_i].any():
                    return False
                dying.append(t_i)
            else:
                moved.append(t_i)
        if len({int(tref[t_i]) for t_i in ball}) > 1:
            return False
        if moved:
            # the collapse's own gate (ops/collapse.py, MMG5_colver's
            # calnew / calold): a weld may not leave a rewritten tet at
            # under 0.3 of the worst it found among them.  A positive
            # volume alone let a weld flatten a tet to 1e-7 of the
            # regular one's quality, 12 of them in one merge of 240k
            # tets, which the tail then had to repair one by one
            # (ROADMAP B1)
            # lint: ok(R2) — moved is a python list of host tet ids
            rows = tet[np.asarray(moved)]
            q_old = tet_quality(vert[rows])
            q_new = tet_quality(vert[np.where(rows == rm, kp, rows)])
            if q_new.min() <= max(0.3 * q_old.min(), 0.0):
                return False
        for t_i in dying:
            tkeep[t_i] = False
        for t_i in moved:
            tet[t_i] = np.where(tet[t_i] == rm, kp, tet[t_i])
            inc[kp].add(t_i)
        vkeep[rm] = False
        return True

    nweld = 0
    for _d, i, j in cand_pairs:
        if not (vkeep[i] and vkeep[j]):
            continue
        if try_weld(j, i) or try_weld(i, j):
            nweld += 1
    return tet, vkeep, tkeep


def grow_shards(shards: Mesh, mets, new_capP: int, new_capT: int):
    """Grow every shard's capacity IN PLACE (stacked axis intact).

    The static-shape analogue of the reference's realloc
    (zaldy_pmmg.c:140-254) without the whole-mesh merge->resplit round
    trip the old regrow path used: buffers are zero/False-padded on the
    capacity axis, so vertex/tet SLOT IDS are preserved — the split-time
    comm tables and frozen-interface contract remain valid, and host
    involvement is O(1) metadata instead of O(mesh).
    """
    capP, capT = shards.vert.shape[1], shards.tet.shape[1]
    dP, dT = new_capP - capP, new_capT - capT
    if dP <= 0 and dT <= 0:
        return shards, mets

    def padP(x, fill=0):
        pad = [(0, 0)] * x.ndim
        pad[1] = (0, max(0, dP))
        return jnp.pad(x, pad, constant_values=fill)

    def padT(x, fill=0):
        pad = [(0, 0)] * x.ndim
        pad[1] = (0, max(0, dT))
        return jnp.pad(x, pad, constant_values=fill)

    out = dataclasses.replace(
        shards,
        vert=padP(shards.vert), vref=padP(shards.vref),
        vtag=padP(shards.vtag), vmask=padP(shards.vmask, False),
        vnrm=padP(shards.vnrm),
        tet=padT(shards.tet), tref=padT(shards.tref),
        tmask=padT(shards.tmask, False), adja=padT(shards.adja, -1),
        ftag=padT(shards.ftag), fref=padT(shards.fref),
        etag=padT(shards.etag))
    return out, padP(mets)


# compiled leading-axis permutation programs keyed by (device ids, leaf
# shapes) — the host-to-host group handoff (parallel/pod.py): one
# x[perm] gather per leaf inside a single jit whose out_shardings keep
# the 'shard' leading axis, so XLA realizes the row moves as
# cross-device (and thereby cross-process) transfers of whole groups
_PERMUTE_CACHE: dict = {}


def permute_shards(shards: Mesh, mets, glo_d, perm, dmesh):
    """Reorder the logical-shard leading axis: new row ``i`` = old row
    ``perm[i]`` (a bijection, G rows per device preserved by the
    caller's plan).  Row CONTENTS — slot ids, and thereby the comm
    tables' local indices — are untouched: the handoff moves whole
    groups, the frozen-interface contract survives by construction.
    Returns (shards', mets', glo_d' | None)."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..utils.compilecache import governed

    leaves = (shards, mets) if glo_d is None else (shards, mets, glo_d)
    flat = jax.tree.leaves(leaves)
    # lint: ok(R2) — device-id metadata + abstract leaf shapes (cache
    # key construction), no device sync
    key = (tuple(d.id for d in np.asarray(dmesh.devices).flat),
           tuple((tuple(x.shape), str(x.dtype)) for x in flat))
    fn = _PERMUTE_CACHE.get(key)
    if fn is None:
        sh = NamedSharding(dmesh, P("shard"))
        fn = governed("mh.group_handoff", budget=8)(
            jax.jit(lambda xs, p: jax.tree.map(lambda x: x[p], xs),
                    out_shardings=sh))
        _PERMUTE_CACHE[key] = fn
    out = fn(leaves, jnp.asarray(np.asarray(perm), jnp.int32))
    if glo_d is None:
        return out[0], out[1], None
    return out


def merge_shards(shards: Mesh, mets=None, return_part: bool = False):
    """Merge stacked shard Meshes back into one host Mesh (+ metric).

    Interface vertices are deduplicated by exact coordinate bytes — valid
    because MG_PARBDY points are frozen during shard-local adaptation.
    With ``return_part``, also returns the source-shard label of every
    merged tet (a valid partition of the merged mesh, ready for
    interface displacement).

    The merged mesh is compact (live rows first) and holds 1.5x its
    content, not ``make_mesh``'s 3x: see where the capacity is chosen.
    """
    nsh = shards.vert.shape[0]
    all_v, all_tag, all_ref, all_met = [], [], [], []
    all_t, all_tref, all_src = [], [], []
    all_ft, all_fr, all_et = [], [], []
    offsets = []
    off = 0
    for s in range(nsh):
        one = jax.tree.map(lambda x: x[s], shards)
        vert, tet, vref, tref, vtag = mesh_to_host(one)
        tm = np.asarray(one.tmask)
        all_v.append(vert)
        all_tag.append(vtag)
        all_ref.append(vref)
        all_t.append(tet + off)
        all_tref.append(tref)
        all_ft.append(np.asarray(one.ftag)[tm])
        all_fr.append(np.asarray(one.fref)[tm])
        all_et.append(np.asarray(one.etag)[tm])
        all_src.append(np.full(len(tet), s, np.int32))
        if mets is not None:
            mh = np.asarray(mets[s])[np.asarray(one.vmask)]
            all_met.append(mh)
        offsets.append(off)
        off += len(vert)
    vert = np.concatenate(all_v)
    vtag = np.concatenate(all_tag)
    vref = np.concatenate(all_ref)
    tet = np.concatenate(all_t)
    tref = np.concatenate(all_tref)
    # face/edge tags travel back with the tets; interface faces become
    # interior (drop the freeze + BDY bits); interface edges keep their
    # true-boundary nature via PARBDYBDY and USER-required status via the
    # absence of MG_NOSURF (REQ without NOSURF was set by the caller, not
    # by the freeze — tag_pmmg.c NOSURF semantics)
    ftag_m = np.concatenate(all_ft)
    fref_m = np.concatenate(all_fr)
    etag_m = np.concatenate(all_et)
    f_ifc = (ftag_m & MG_PARBDY) != 0
    f_user = f_ifc & ((ftag_m & MG_NOSURF) == 0) & \
        ((ftag_m & MG_REQ) != 0)
    ftag_m[f_ifc] &= ~np.uint32(PARBDY_TAGS)
    ftag_m[f_user] |= MG_REQ
    e_ifc = (etag_m & MG_PARBDY) != 0
    e_truebdy = (etag_m & MG_PARBDYBDY) != 0
    e_user = e_ifc & ((etag_m & MG_NOSURF) == 0) & \
        ((etag_m & MG_REQ) != 0)
    etag_m[e_ifc] &= ~np.uint32(PARBDY_TAGS | MG_PARBDYBDY)
    etag_m[e_ifc & e_truebdy] |= MG_BDY
    etag_m[e_user] |= MG_REQ

    # dedup PARBDY vertices by coordinate bytes
    is_ifc = (vtag & MG_PARBDY) != 0
    keys = vert.astype(np.float64).tobytes()
    rows = np.frombuffer(keys, dtype=np.dtype((np.void, 24)))
    uniq, first_idx, inv = np.unique(rows, return_index=True,
                                     return_inverse=True)
    # canonical id: first occurrence; only merge interface copies
    canon = first_idx[inv]
    remap = np.arange(len(vert))
    remap[is_ifc] = canon[is_ifc]
    # drop PARBDY tags after merge (interfaces no longer exist) but keep
    # true-boundary info via MG_PARBDYBDY
    keep = np.zeros(len(vert), bool)
    keep[remap] = True
    new_id = np.cumsum(keep) - 1
    tet = new_id[remap[tet]].astype(np.int32)
    vtag2 = vtag[keep].copy()
    was_truebdy = (vtag2 & MG_PARBDYBDY) != 0
    was_parbdy = (vtag2 & MG_PARBDY) != 0
    was_user_req = was_parbdy & ((vtag2 & MG_NOSURF) == 0) & \
        ((vtag2 & MG_REQ) != 0)
    vtag2 &= ~np.uint32(PARBDY_TAGS | MG_PARBDYBDY)
    vtag2[was_truebdy] |= MG_BDY
    vtag2[was_parbdy & ~was_truebdy] &= ~np.uint32(MG_BDY)
    vtag2[was_user_req] |= MG_REQ

    vert_k = vert[keep]
    vref_k = vref[keep]
    met_k = np.concatenate(all_met)[keep] if mets is not None else None
    src_k = np.concatenate(all_src)
    # sequential weld of near-coincident interior pairs left by
    # independent refinement across the frozen interface (see
    # _weld_close_pairs — the batched collapse deadlocks on these)
    tet, vkeep2, tkeep2 = _weld_close_pairs(
        vert_k, tet, vtag2, met_k, tref, ftag_m, etag_m)
    if not (vkeep2.all() and tkeep2.all()):
        nid = np.cumsum(vkeep2) - 1
        tet = nid[tet[tkeep2]].astype(np.int32)
        tref = tref[tkeep2]
        ftag_m = ftag_m[tkeep2]
        fref_m = fref_m[tkeep2]
        etag_m = etag_m[tkeep2]
        src_k = src_k[tkeep2]
        vert_k = vert_k[vkeep2]
        vref_k = vref_k[vkeep2]
        vtag2 = vtag2[vkeep2]
        if met_k is not None:
            met_k = met_k[vkeep2]

    # Capacity of a merged mesh: 1.5x its content, chosen HERE and from
    # the live counts alone.  Nobody grows a merged mesh in place: it is
    # re-split into shards that get their own capacity, read (interface
    # displacement), or run through the merged tail (driver: polish,
    # repair, fem), which shrinks it or adds a percent or two; and every
    # wave of that tail sorts, gathers and scatters all capT rows, so
    # make_mesh's 3x doubled the tail's cost for nothing (PERF.md, PR 29).
    # Why 1.5x and not less:
    # - the polish's top-K budget is (3 n_t) // 2 rows of CONTENT
    #   (driver.polish_budget); at this capacity no top-K is wider than
    #   the arrays it reads, and under it the K-wide machinery, not the
    #   padding, is what a wave costs (1.25x measured no faster);
    # - the n_t // 2 free rows are the most ONE allocating wave can take:
    #   a 2-3 swap claims two live tets for itself and allocates one row,
    #   a 6-ring swap claims six and allocates two (n_t // 3).  Only both
    #   at that ceiling in one polish wave would run the pool dry, and
    #   then the winners that find no row are deferred to the next wave,
    #   not lost (`win & fits`, ops/swap.py and ops/swapgen.py).  Measured:
    #   the busiest wave of a cell applies 1,386 swaps on 32,592 tets
    #   against 16,296 free rows, the fem rounds add 1.3 % and regrow on
    #   overflow (driver._finish_run; tests/test_merged_tail.py).
    n_p, n_t = len(vert_k), len(tet)
    m = make_mesh(vert_k, tet, vref=vref_k, tref=tref,
                  capP=max(64, (3 * n_p) // 2), capT=max(64, (3 * n_t) // 2))
    vtag_full = np.zeros(m.capP, np.uint32)
    vtag_full[: len(vtag2)] = vtag2
    ftag_full = np.zeros((m.capT, 4), np.uint32)
    ftag_full[: len(ftag_m)] = ftag_m
    fref_full = np.zeros((m.capT, 4), np.int32)
    fref_full[: len(fref_m)] = fref_m
    etag_full = np.zeros((m.capT, 6), np.uint32)
    etag_full[: len(etag_m)] = etag_m
    m = dataclasses.replace(m, vtag=jnp.asarray(vtag_full),
                            ftag=jnp.asarray(ftag_full),
                            fref=jnp.asarray(fref_full),
                            etag=jnp.asarray(etag_full))
    m = boundary_edge_tags(build_adjacency(m))
    out_met = None
    if mets is not None:
        full = np.zeros((m.capP,) + met_k.shape[1:], met_k.dtype)
        full[: len(met_k)] = met_k
        out_met = jnp.asarray(full)
    if return_part:
        return m, out_met, src_k
    return m, out_met
