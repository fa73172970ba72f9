"""Batched topology swaps (3-2 edge swap, 2-3 face swap, 2-2 boundary swap).

Reference behavior: Mmg's ``MMG5_swpmsh``/``MMG3D_swpmshcpy`` remove bad
configurations by re-triangulating small cavities around an edge or face
when the worst quality strictly improves; boundary edges are swapped by
``MMG5_swpbdy`` after ``MMG5_chkswpbdy`` validates the surface retiling;
the frozen-interface contract (tag_pmmg.c:39-124) keeps parallel entities
untouched.  Improvement gate: new worst quality > SWAP_GAIN * old worst
(Mmg uses 1.053).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mesh import Mesh
from ..core.constants import (
    EPSD, QUAL_FLOOR, MG_BDY, MG_GEO, MG_NOM, MG_OPNBDY, MG_PARBDY,
    MG_REF, MG_REQ)
from .edges import (unique_edges, claim_channels, claim_shells, NEG_INF,
                    PACK_LIMIT, PRI_MIN)
from .quality import quality_from_points

SWAP_GAIN = 1.053

# local edge index for a corner pair (i, j) — inverse of IARE
_EDGE_OF = np.zeros((4, 4), np.int32)
for _e, (_i, _j) in enumerate([[0, 1], [0, 2], [0, 3],
                               [1, 2], [1, 3], [2, 3]]):
    _EDGE_OF[_i, _j] = _EDGE_OF[_j, _i] = _e


class SwapResult(NamedTuple):
    mesh: Mesh
    nswap: jax.Array
    deferred: jax.Array = None  # scalar bool: candidates exceeded the
    #                 top-K budget; they wait for the next wave
    # with a worklist only (ops/worklist; swap_edges_wave):
    keep: jax.Array = None      # [capT] bool, rows that stay on the list
    ncand: jax.Array = None     # candidate rows the top-K selected
    nlist: jax.Array = None     # of them on the list: what was judged


class _EdgeRows(NamedTuple):
    """Per candidate row of swap_edges_wave, what the claims, the
    duplicate veto and the apply read."""
    cand: jax.Array     # [k] passed every gate
    viable: jax.Array   # [k] passed every gate that reads its shell alone
    base32: jax.Array
    base22: jax.Array
    q_old: jax.Array
    q_new: jax.Array
    s0: jax.Array       # [k] shell rows (s2 only of a 3-2 candidate)
    s1: jax.Array
    s2: jax.Array
    x0: jax.Array       # [k] the new edge's ends
    x1: jax.Array
    new_a: jax.Array    # [k, 4] the two new tets, then their tags
    new_b: jax.Array
    ftag_a: jax.Array
    fref_a: jax.Array
    etag_a: jax.Array
    ftag_b: jax.Array
    fref_b: jax.Array
    etag_b: jax.Array


def _met6(met):
    """Aniso: packed tensors; iso: None — quality is evaluated in
    Euclidean space exactly like Mmg's ``MMG5_caltet_iso`` (the constant
    local scaling cancels in Q), which skips the [*,4,6] metric gathers
    that dominate swap cost on TPU."""
    return None if met.ndim == 1 else met


def swap_facesort_enabled() -> bool:
    """PARMMG_SWAP_FACESORT (default on): pair swap23 directly off the
    face-sort records instead of materializing ``adja`` with a full
    ``build_adjacency`` between the edge-swap and 2-3 waves — swap23 is
    the only cycle-interior adja reader, and the facesort pairing is
    bit-identical (see _pair_fields_facesort).  TRACE-TIME read: both
    paths produce the same bits, so a stale jit cache entry is only a
    perf choice, never a correctness one.

    Unset means on where the program being traced is PLACED on a TPU,
    off elsewhere: the CPU backend's sort is slow enough that the face
    re-sort costs more than the adja rebuild it replaces (~+7% s/cycle),
    and a TPU process places its tail on the host's CPU backend
    (``host_staging``), where the process default chose wrongly.  ``1``/
    ``0`` force the path on any backend (the parity tests do, on CPU)."""
    import os
    v = os.environ.get("PARMMG_SWAP_FACESORT", "")
    if v == "":
        from ..utils.placement import placed_on_tpu
        return placed_on_tpu()
    return v != "0"


def swap_edges_wave(mesh: Mesh, met: jax.Array, enable32: bool = True,
                    enable22: bool = True,
                    flat_tol: float = 1e-5,
                    hausd: float | None = None,
                    budget_div: int = 8,
                    budget: int | None = None,
                    worklist=None, et=None) -> SwapResult:
    """Combined edge-swap wave: 3-2 interior + 2-2 boundary, ONE pass.

    Both swaps share the same cavity shape — edge (a,b) is replaced by two
    tets A=(x0,x1,x2,a), B=(x0,x1,x2,b) overwriting the first two shell
    slots — so they share one edge table, one batched position lookup, one
    stacked quality call and one claim resolution (each distinct XLA op
    carries a multi-ms fixed cost on this device, scripts/tpu_microbench.py).

    3-2 (Mmg ``MMG5_swap``): interior untagged edge with a 3-tet shell
    ring (p,q,r); (x0,x1,x2)=(p,q,r); the third shell slot dies.  The
    cavity MAY touch the boundary elsewhere: every exterior face/edge
    survives in A/B and its tags are routed through.

    2-2 (Mmg ``MMG5_swpbdy``/``chkswpbdy``): regular boundary edge whose
    2-tet shell covers a planar boundary quad (a,x0,b,x1) with shared
    interior vertex x2=c; the surface diagonal flips to (x0,x1) —
    surface-exact within ``flat_tol`` of the local scale (the hausd
    analogue for piecewise-flat geometry); both gates carry float32 noise
    floors (cross products of coordinate differences err with
    eps32*|coords|, which swamps a purely relative tolerance on exactly
    the thin quads this swap targets).

    Top-K compaction (the wave's cost lever, PERF.md section 5): the
    cheap candidacy masks are computed at full [6*capT] width, then only
    the K = capT/``budget_div`` candidates with the WORST current shell
    quality go through the heavy role-derivation / gate / routing /
    scatter machinery.  Claims resolve against the global tet pool, so
    exactness under simultaneous application is unchanged; candidates
    past the budget are simply deferred to the next wave (waves repeat
    until quiet, and swaps exist to fix the worst elements first — the
    same prioritization Mmg's quality-driven sweeps apply).

    ``worklist``: an ``ops/worklist.Dirty``, what changed since this
    kernel last judged the mesh.  Only the candidates it lists go
    through the candidate stage, in chunks as wide as the list; the
    result is the full evaluation's to the bit while the caller keeps
    the list by that module's rules.  None (the cycle block) traces the
    stage once over all K rows, the program it always was.

    ``et``: the mesh's edge table (``unique_edges(mesh)``: three shell
    slots) where the caller has it, as ``collapse_wave`` takes one: the
    merged polish derives it from the sort it carries (ops/topo_incr).
    None builds it here.
    """
    capT, capP = mesh.capT, mesh.capP
    if et is None:
        et = unique_edges(mesh)
    m6 = _met6(met)
    Efull = et.ev.shape[0]
    eof = jnp.asarray(_EDGE_OF)

    # ---- cheap full-width candidacy + worst-shell priority ---------------
    ft0_, ft1_, ft2_ = et.shell3[:, 0], et.shell3[:, 1], et.shell3[:, 2]
    q_tet = quality_from_points(
        mesh.vert[mesh.tet], None if m6 is None else m6[mesh.tet])
    s0f = jnp.clip(ft0_, 0, capT - 1)
    s1f = jnp.clip(ft1_, 0, capT - 1)
    s2f = jnp.clip(ft2_, 0, capT - 1)
    qs0 = jnp.where(ft0_ >= 0, q_tet[s0f], jnp.inf)
    qs1 = jnp.where(ft1_ >= 0, q_tet[s1f], jnp.inf)
    qs2 = jnp.where(ft2_ >= 0, q_tet[s2f], jnp.inf)
    # STATIC gates go into the pre-mask at full width: a candidate that
    # can never pass (wrong tref pairing, missing shell slots) must not
    # pin a top-K slot wave after wave (it would never be deferred — the
    # mesh doesn't change under it).  Only genuinely geometric gates
    # (planarity, quality) stay post-compaction.
    pair_ok_f = (ft0_ >= 0) & (ft1_ >= 0) & \
        (mesh.tref[s0f] == mesh.tref[s1f])
    if enable32:
        pre32 = et.emask & (et.nshell == 3) & (et.etag == 0) & \
            pair_ok_f & (ft2_ >= 0) & (mesh.tref[s0f] == mesh.tref[s2f])
    else:
        pre32 = jnp.zeros(Efull, bool)
    if enable22:
        frozen22 = (et.etag & (MG_GEO | MG_REQ | MG_PARBDY | MG_NOM |
                               MG_REF | MG_OPNBDY)) != 0
        pre22 = et.emask & (et.nshell == 2) & \
            ((et.etag & MG_BDY) != 0) & ~frozen22 & pair_ok_f
    else:
        pre22 = jnp.zeros(Efull, bool)
    pre = pre32 | pre22
    from .edges import wave_budget, topk_prep3
    K = min(Efull, wave_budget(capT, budget_div, budget))
    # fused scoring prep (exact q_shell = min(qs0, min(qs1, qs2)) chain)
    neg, npre = topk_prep3(pre, qs0, qs1, qs2)
    defer = npre > K
    # top-K worst shells without a full-width argsort
    _, sel = jax.lax.top_k(neg, K)

    def stage(sel):
        """The candidate stage on compacted rows, each row by itself:
        roles, positions, gates, qualities and tag routing.  Every value
        read is one of the row's shell (its tets' vertex ids, tags and
        references, their vertices' coordinates and metric) but the 2-2
        swap's ``exists`` probe, which asks the whole edge table."""
        # ---- compacted columns ----------------------------------------------
        ev_c = et.ev[sel]
        shell3_c = et.shell3[sel]
        E = sel.shape[0]
        ar = jnp.arange(E)
        false_e = jnp.zeros(E, bool)

        t0, t1, t2 = shell3_c[:, 0], shell3_c[:, 1], shell3_c[:, 2]
        s0 = jnp.clip(t0, 0, capT - 1)
        s1 = jnp.clip(t1, 0, capT - 1)
        s2 = jnp.clip(t2, 0, capT - 1)
        a = jnp.clip(ev_c[:, 0], 0, capP - 1)
        b = jnp.clip(ev_c[:, 1], 0, capP - 1)
        tv0 = mesh.tet[s0]
        tv1 = mesh.tet[s1]

        # pair/tref gates already folded into the pre-masks (full width)
        base32 = pre32[sel] if enable32 else false_e
        base22 = pre22[sel] if enable22 else false_e

        # ---- role derivation -------------------------------------------------
        # s0's two non-(a,b) corners y1, y2
        is_ab0 = (tv0 == a[:, None]) | (tv0 == b[:, None])
        ordr = jnp.argsort(is_ab0.astype(jnp.int32), axis=1, stable=True)
        y1 = tv0[ar, ordr[:, 0]]
        y2 = tv0[ar, ordr[:, 1]]
        # 2-2 roles: c = the one shared with T2, p = the other, q = T2's 4th
        y1_in1 = jnp.any(tv1 == y1[:, None], axis=1)
        y2_in1 = jnp.any(tv1 == y2[:, None], axis=1)
        c22 = jnp.where(y1_in1, y1, y2)
        p22 = jnp.where(y1_in1, y2, y1)
        is_abc1 = (tv1 == a[:, None]) | (tv1 == b[:, None]) | \
            (tv1 == c22[:, None])
        q22 = tv1[ar, jnp.argmax(~is_abc1, axis=1)]
        # degenerate shells (edge shared without a shared face) rejected
        base22 = base22 & (y1_in1 ^ y2_in1) & \
            (jnp.sum(is_abc1.astype(jnp.int32), axis=1) == 3)
        # 3-2 roles: ring (p,q) from s0, r from s1; relabel (s1,s2) as
        # (t_pr, t_qr) by which one contains p
        p32, q32 = y1, y2
        is_pq1 = (tv1 == p32[:, None]) | (tv1 == q32[:, None])
        r32 = tv1[ar, jnp.argmax(~(is_abc1 | is_pq1), axis=1)]
        s1_has_p = jnp.any(tv1 == p32[:, None], axis=1)
        t_pr = jnp.where(s1_has_p, s1, s2)
        t_qr = jnp.where(s1_has_p, s2, s1)

        # unified roles: new tets A=(x0,x1,x2,a), B=(x0,x1,x2,b); tag sources
        # u1 (holds x0,x2 faces/edges) and u2 (holds x1,x2)
        x0 = jnp.where(base32, p32, p22)
        x1 = jnp.where(base32, q32, q22)
        x2 = jnp.where(base32, r32, c22)
        u1 = jnp.where(base32, t_pr, s0)
        u2 = jnp.where(base32, t_qr, s1)
        tu1 = mesh.tet[u1]
        tu2 = mesh.tet[u2]

        # ---- batched positions of (a, b, x0, x1, x2) in s0/u1/u2 -------------
        tgt = jnp.stack([a, b, x0, x1, x2], axis=1)            # [E,5]

        def pos5(tv):
            eqm = tv[:, None, :] == tgt[:, :, None]            # [E,5,4]
            return (jnp.argmax(eqm, axis=2).astype(jnp.int32),
                    jnp.any(eqm, axis=2))

        P0, in0 = pos5(tv0)
        P1, in1 = pos5(tu1)
        P2, in2 = pos5(tu2)
        # 3-2 ring sanity: u1 must hold {x0,x2}, u2 {x1,x2}
        ring_ok = in1[:, 2] & in1[:, 4] & in2[:, 3] & in2[:, 4]
        base32 = base32 & ring_ok
        base22 = base22 & ring_ok          # holds by construction; belt+braces

        # ---- gathered tag/ref rows (all routing reads go through these) ------
        et0, et1r, et2r = mesh.etag[s0], mesh.etag[u1], mesh.etag[u2]
        ft0, ft1r, ft2r = mesh.ftag[s0], mesh.ftag[u1], mesh.ftag[u2]
        fr0, fr1r, fr2r = mesh.fref[s0], mesh.fref[u1], mesh.fref[u2]

        def ecol(rows, pi, pj):
            return jnp.take_along_axis(rows, eof[pi, pj][:, None], axis=1)[:, 0]

        def fcol(rows, pi):
            return jnp.take_along_axis(rows, pi[:, None], axis=1)[:, 0]

        # ---- 2-2 gates: boundary faces, planarity, area, duplicate edge ------
        if enable22:
            ft_bdy1 = fcol(ft0, P0[:, 4])          # T1 face opposite c
            ft_bdy2 = fcol(ft2r, P2[:, 4])         # T2 face opposite c
            fr_bdy1 = fcol(fr0, P0[:, 4])
            fr_bdy2 = fcol(fr2r, P2[:, 4])
            bad_face_bits = MG_REQ | MG_PARBDY | MG_NOM | MG_OPNBDY
            base22 = base22 & ((ft_bdy1 & MG_BDY) != 0) & \
                ((ft_bdy2 & MG_BDY) != 0) & \
                (((ft_bdy1 | ft_bdy2) & bad_face_bits) == 0) & \
                (ft_bdy1 == ft_bdy2) & (fr_bdy1 == fr_bdy2) & \
                (fcol(ft0, P0[:, 2]) == 0) & (fcol(ft2r, P2[:, 3]) == 0)
            newf = ft_bdy1
            newfr = fr_bdy1
            newe22 = jnp.uint32(MG_BDY) | (newf & MG_REF)

            pa_, pb_ = mesh.vert[a], mesh.vert[b]
            pp_, pq_ = mesh.vert[x0], mesh.vert[x1]
            pc_ = mesh.vert[x2]
            n_abp = jnp.cross(pb_ - pa_, pp_ - pa_)
            n_abq = jnp.cross(pq_ - pa_, pb_ - pa_)
            nn = jnp.sqrt(jnp.sum(n_abp * n_abp, -1)) + EPSD
            hloc = jnp.sqrt(jnp.maximum(jnp.maximum(
                jnp.sum((pb_ - pa_) ** 2, -1), jnp.sum((pp_ - pa_) ** 2, -1)),
                jnp.sum((pq_ - pa_) ** 2, -1)))
            eps_c = jnp.finfo(mesh.vert.dtype).eps
            cmax = jnp.max(jnp.stack([jnp.max(jnp.abs(pt_), -1) for pt_ in
                                      (pa_, pb_, pc_, pp_, pq_)]), axis=0)
            off_plane = jnp.abs(jnp.sum(n_abp * (pq_ - pa_), -1)) / nn
            noise_op = 32.0 * eps_c * cmax * hloc * hloc / nn
            # hausd relaxes the surface-exactness requirement to the Mmg
            # approximation tolerance: the flip changes the surface by at
            # most the quad's out-of-plane deviation
            tol_op = flat_tol * hloc + noise_op
            if hausd is not None:
                tol_op = jnp.maximum(tol_op, hausd)
            base22 = base22 & (off_plane <= tol_op)
            area = lambda nv: 0.5 * jnp.sqrt(jnp.sum(nv * nv, -1))
            a_old = area(n_abp) + area(n_abq)
            a_new = area(jnp.cross(pq_ - pp_, pa_ - pp_)) + \
                area(jnp.cross(pq_ - pp_, pb_ - pp_))
            noise_ar = 32.0 * eps_c * cmax * hloc
            tol_ar = 1e-5 * (a_old + EPSD) + noise_ar
            if hausd is not None:
                # area may legitimately change by ~ hausd * perimeter when
                # the quad is curved within tolerance
                tol_ar = jnp.maximum(tol_ar, hausd * hloc)
            base22 = base22 & (jnp.abs(a_old - a_new) <= tol_ar)
            # the flipped diagonal must not already exist (duplicate edge =>
            # non-manifold surface).  Packed int32 binary search when ids fit
            # (edges.PACK_LIMIT); sort-join fallback otherwise (no x64).
            from .edges import sort_pairs, segmented_or
            kmin = jnp.minimum(x0, x1)
            kmax = jnp.maximum(x0, x1)
            if capP <= PACK_LIMIT:
                i32max = jnp.iinfo(jnp.int32).max
                # the table's internal sort already produced ascending packed
                # keys (duplicates included — harmless for the existence
                # probe); reuse them instead of re-sorting [6*capT] keys
                if et.skey.shape[0] == Efull:
                    ekey = et.skey
                else:
                    ekey = jnp.sort(jnp.where(
                        et.emask, et.ev[:, 0] * capP + et.ev[:, 1], i32max))
                pkey = kmin * capP + kmax
                loc = jnp.searchsorted(ekey, pkey)
                exists = ekey[jnp.clip(loc, 0, Efull - 1)] == pkey
            else:
                # sort-join over full table + the K compacted candidates
                aa = jnp.concatenate([jnp.where(et.emask, et.ev[:, 0], 0),
                                      kmin])
                bb = jnp.concatenate([jnp.where(et.emask, et.ev[:, 1], 0),
                                      kmax])
                vv = jnp.concatenate([et.emask, base22])
                n_all = Efull + E
                order, ka_s, _, first, _ = sort_pairs(aa, bb, vv, capP)
                # a valid slot's sorted key is under INT32_MAX
                is_edge = (order < Efull) & \
                    (ka_s != jnp.iinfo(jnp.int32).max)
                has_edge = segmented_or(first, is_edge.astype(jnp.uint32))
                is_last = jnp.concatenate([first[1:], jnp.array([True])])
                seg = jax.lax.associative_scan(
                    jnp.maximum, jnp.where(first, jnp.arange(n_all), 0))
                total = jnp.zeros(n_all, jnp.uint32).at[
                    jnp.where(is_last, seg, n_all)].set(
                    has_edge, mode="drop", unique_indices=True)
                exists = jnp.zeros(E, bool).at[
                    jnp.where(order >= Efull, order - Efull, E)].set(
                    total[seg] > 0, mode="drop")
            base22x = base22        # every gate but this one reads the
            base22 = base22 & ~exists   # shell alone (ops/worklist)
        else:
            newf = jnp.zeros(E, jnp.uint32)
            newfr = jnp.zeros(E, jnp.int32)
            newe22 = jnp.zeros(E, jnp.uint32)
            base22x = base22

        # ---- 3-2 gate: the vanishing interior faces must be untagged ---------
        if enable32:
            from ..core.constants import EDGE_FACES
            cfaces = jnp.asarray(EDGE_FACES)     # faces containing IARE edge
            face_clean = jnp.ones(E, bool)
            for rows, Pm in ((ft0, P0), (ft1r, P1), (ft2r, P2)):
                lae = eof[Pm[:, 0], Pm[:, 1]]
                for k in range(2):
                    face_clean = face_clean & \
                        (fcol(rows, cfaces[lae, k]) == 0)
            base32 = base32 & face_clean

        cand = base32 | base22

        # ---- geometric validity: a, b astride the new interior plane ---------
        def signed_vol(v0, v1, v2, v3):
            q0, q1, q2, q3 = (mesh.vert[v0], mesh.vert[v1], mesh.vert[v2],
                              mesh.vert[v3])
            return jnp.sum((q1 - q0) * jnp.cross(q2 - q0, q3 - q0), -1)

        sv_a = signed_vol(x0, x1, x2, a)
        sv_b = signed_vol(x0, x1, x2, b)
        cand = cand & (sv_a * sv_b < 0) & (jnp.abs(sv_a) > EPSD) & \
            (jnp.abs(sv_b) > EPSD)
        flip_a = sv_a < 0
        flip_b = sv_b < 0

        def orient(v0, v1, v2, v3, flip):
            w0 = jnp.where(flip, v1, v0)
            w1 = jnp.where(flip, v0, v1)
            return jnp.stack([w0, w1, v2, v3], axis=1)

        new_a = orient(x0, x1, x2, a, flip_a)
        new_b = orient(x0, x1, x2, b, flip_b)

        # ---- quality gate: one stacked call for both new tets ----------------
        # (q_tet computed once above, at the priority step)
        q_old = jnp.minimum(q_tet[s0], q_tet[s1])
        q_old = jnp.minimum(q_old, jnp.where(base32, q_tet[s2], jnp.inf))
        new_ab = jnp.concatenate([new_a, new_b])
        q_ab = quality_from_points(
            mesh.vert[new_ab], None if m6 is None else m6[new_ab])
        q_new = jnp.minimum(q_ab[:E], q_ab[E:])
        cand = cand & (q_new > jnp.maximum(SWAP_GAIN * q_old, QUAL_FLOOR))
        if worklist is not None:
            viable = (base32 | base22x) & (sv_a * sv_b < 0) & \
                (jnp.abs(sv_a) > EPSD) & (jnp.abs(sv_b) > EPSD) & \
                (q_new > jnp.maximum(SWAP_GAIN * q_old, QUAL_FLOOR))

        # ---- tag routing (base corner order (x0,x1,x2,y)) --------------------
        # faces: col0 (opp x0) <- u2 opposite the vanished vertex; col1 <- u1;
        # col2 <- s0 for 3-2 / the NEW boundary face for 2-2; col3 interior.
        # edges (IARE): (x0x1, x0x2, x0y, x1x2, x1y, x2y).  A flip of
        # (x0,x1) permutes face cols (0,1) and edge cols (0,3,4,1,2,5).
        zero_u = jnp.zeros(E, jnp.uint32)
        zero_i = jnp.zeros(E, jnp.int32)

        def route_f(col0, col1, col2, zero, flip):
            w0 = jnp.where(flip, col1, col0)
            w1 = jnp.where(flip, col0, col1)
            return jnp.stack([w0, w1, col2, zero], axis=1)

        def route_e(cols, flip):
            flipped = [cols[0], cols[3], cols[4], cols[1], cols[2], cols[5]]
            return jnp.stack([jnp.where(flip, f, n)
                              for n, f in zip(cols, flipped)], axis=1)

        def routed(y_idx):
            """Face/edge/ref routing for new tet (x0,x1,x2,y); y_idx: 0=a 1=b.

            Inherited faces are the old faces OPPOSITE the vanished endpoint
            (tet A keeps the faces that b vanished from), so face columns use
            the other endpoint's positions; edges incident to y use y's own.
            """
            py0, py1, py2 = P0[:, y_idx], P1[:, y_idx], P2[:, y_idx]
            po0, po1, po2 = (P0[:, 1 - y_idx], P1[:, 1 - y_idx],
                             P2[:, 1 - y_idx])
            ftag_n = route_f(
                fcol(ft2r, po2), fcol(ft1r, po1),
                jnp.where(base32, fcol(ft0, po0), newf), zero_u,
                flip_a if y_idx == 0 else flip_b)
            fref_n = route_f(
                fcol(fr2r, po2), fcol(fr1r, po1),
                jnp.where(base32, fcol(fr0, po0), newfr), zero_i,
                flip_a if y_idx == 0 else flip_b)
            e0 = jnp.where(base32, ecol(et0, P0[:, 2], P0[:, 3]), newe22)
            e1 = ecol(et1r, P1[:, 2], P1[:, 4])
            e2 = ecol(et0, P0[:, 2], py0)
            e3 = ecol(et2r, P2[:, 3], P2[:, 4])
            e4 = jnp.where(base32, ecol(et0, P0[:, 3], py0),
                           ecol(et2r, P2[:, 3], py2))
            e5 = ecol(et2r, P2[:, 4], py2) | \
                jnp.where(base22, ecol(et0, P0[:, 4], py0), 0)
            etag_n = route_e([e0, e1, e2, e3, e4, e5],
                             flip_a if y_idx == 0 else flip_b)
            return ftag_n, fref_n, etag_n

        ftag_a, fref_a, etag_a = routed(0)
        ftag_b, fref_b, etag_b = routed(1)

        return _EdgeRows(
            cand, viable if worklist is not None else cand, base32, base22,
            q_old, q_new, s0, s1, s2, x0, x1, new_a, new_b,
            ftag_a, fref_a, etag_a, ftag_b, fref_b, etag_b)

    pos = None
    if worklist is None:
        rows = stage(sel)
    else:
        from . import worklist as wl
        # the compaction line: ahead of it the wave costs its capacity,
        # after it what the list holds
        sh0 = et.shell3[sel]
        listed = pre[sel] & wl.on_list(
            worklist, jnp.clip(sh0, 0, capT - 1), sh0 >= 0,
            jnp.clip(et.ev[sel, 0], 0, capP - 1),
            jnp.clip(et.ev[sel, 1], 0, capP - 1))
        pos, nlist = wl.listed_first(listed)
        sel = sel[pos]
        # past PACK_LIMIT the duplicate-diagonal probe is a sort-join
        # over the whole edge table: one chunk, so it runs once
        rows = wl.staged(stage, sel, nlist,
                         wl.CHUNKS if capP <= PACK_LIMIT else 1)
        on = jnp.arange(K) < nlist
        rows = rows._replace(cand=rows.cand & on, viable=rows.viable & on)
    (cand, viable, base32, base22, q_old, q_new, s0, s1, s2, x0, x1,
     new_a, new_b, ftag_a, fref_a, etag_a, ftag_b, fref_b, etag_b) = rows
    E = K

    # ---- claims: s0, s1 (+ s2 for 3-2), exclusively ----------------------
    s2eff = jnp.where(base32, s2, s0)        # duplicate claim is harmless
    win = claim_shells(q_new - q_old, cand, (s0, s1, s2eff), capT, pos=pos)

    if enable22:
        # same-wave duplicate-diagonal veto: two 2-2 winners flipping to
        # the SAME new edge (x0,x1) — disjoint shells, so claims allow it
        # — would give that edge four boundary faces (non-manifold).  The
        # pre-wave existence check cannot see same-wave creations; keep
        # only the first winner per key (sort is ~free on this device).
        from .edges import sort_pairs as _sp
        win22 = win & base22
        order_d, ka_d, _, first_d, _ = _sp(
            jnp.minimum(x0, x1), jnp.maximum(x0, x1), win22, capP)
        # a winner's sorted key is under INT32_MAX: no fetch of win22
        dup_sorted = (ka_d != jnp.iinfo(jnp.int32).max) & ~first_d
        dup = jnp.zeros(E, bool).at[order_d].set(dup_sorted,
                                                 unique_indices=True)
        win = win & ~dup

    # ---- apply: one concatenated scatter per array -----------------------
    w0i = jnp.where(win, s0, capT)
    w1i = jnp.where(win, s1, capT)
    idx2 = jnp.concatenate([w0i, w1i])
    tet = mesh.tet.at[idx2].set(
        jnp.concatenate([new_a, new_b]), mode="drop")
    ftag = mesh.ftag.at[idx2].set(
        jnp.concatenate([ftag_a, ftag_b]), mode="drop")
    fref = mesh.fref.at[idx2].set(
        jnp.concatenate([fref_a, fref_b]), mode="drop")
    etag = mesh.etag.at[idx2].set(
        jnp.concatenate([etag_a, etag_b]), mode="drop")
    tmask = mesh.tmask.at[jnp.where(win & base32, s2, capT)].set(
        False, mode="drop")
    nsw = jnp.sum(win.astype(jnp.int32))
    out = dataclasses.replace(mesh, tet=tet, tmask=tmask, ftag=ftag,
                              fref=fref, etag=etag, nelem=mesh.nelem)
    if worklist is None:
        return SwapResult(out, nsw, defer)
    # what stays on the list passed every gate of its own shell and did
    # not apply: a claim loser, a duplicate diagonal, one ``exists`` alone
    # refused (a swap elsewhere can remove that edge)
    return SwapResult(
        out, nsw, defer, wl.keep_rows(viable & ~win, s0, npre, capT),
        jnp.minimum(npre, K), nlist)


def swap32_wave(mesh: Mesh, met: jax.Array) -> SwapResult:
    """3-2 interior edge swap only (see swap_edges_wave)."""
    return swap_edges_wave(mesh, met, enable32=True, enable22=False)


def swap22_wave(mesh: Mesh, met: jax.Array, flat_tol: float = 1e-5,
                hausd: float | None = None) -> SwapResult:
    """2-2 boundary edge swap only (see swap_edges_wave)."""
    return swap_edges_wave(mesh, met, enable32=False, enable22=True,
                           flat_tol=flat_tol, hausd=hausd)


def _pair_fields_adja(mesh: Mesh, q_tet, capT):
    """Legacy swap23 pairing off the materialized ``adja`` matrix:
    per-tet candidate fields (fstar, t2_full, f2_full, cand_full)."""
    adja = mesh.adja
    nb = adja >> 2
    nf = adja & 3
    valid = (adja >= 0) & mesh.tmask[:, None]
    nb_s = jnp.clip(nb, 0, capT - 1)
    # candidate faces, owned by the lower tet id; the swapped face itself
    # must be untagged (strictly interior) — exterior faces/edges of the
    # cavity may be tagged, their tags are routed to the new fan below
    tid = jnp.arange(capT, dtype=jnp.int32)[:, None]
    own = valid & (tid < nb) & mesh.tmask[nb_s]
    nf_s = jnp.clip(nf, 0, 3)
    own = own & (mesh.ftag == 0) & \
        (mesh.ftag[nb_s, nf_s] == 0)
    q_nb = jnp.where(own, q_tet[nb_s], jnp.inf)          # [T,4]
    fstar = jnp.argmin(q_nb, axis=1).astype(jnp.int32)   # [T]
    arT = jnp.arange(capT)
    t2_full = nb_s[arT, fstar]
    f2_full = nf_s[arT, fstar]
    cand_full = own[arT, fstar]
    return fstar, t2_full, f2_full, cand_full


def _pair_fields_facesort(mesh: Mesh, q_tet, capT):
    """Swap23 pairing DIRECTLY off the face-sort records — no [capT,4]
    ``adja`` materialization, no per-tet [T,4] argmin machinery.

    Bit-parity with :func:`_pair_fields_adja` on every row the wave can
    consume:

    * a sorted slot is ``own`` iff its legacy (t, f) entry is: matched
      twins are exactly the ``adja >= 0`` entries (dead tets carry the
      INT32_MAX key and never match, so both twins are live — the
      ``valid``/``tmask[nb]`` conjuncts of the legacy mask hold by
      construction), and the ownership/ftag gates are evaluated on the
      same values;
    * the per-tet winner face reproduces ``argmin(q_nb, axis=1)``'s
      first-index tie-break exactly: the two-channel scatter-max with
      channels (-q_twin, -f) picks the minimum twin quality and, among
      float-equal minima, the smallest local face id (``scatter_argmax2``
      is exact — the tie channel is unique per (tet, face));
    * non-candidate rows default to 0 instead of the legacy clipped
      garbage; every downstream read of those rows is masked by
      ``cand_full`` (claims, scatters and the duplicate-edge veto all
      route masked rows to the drop sentinel), so the applied mesh is
      bit-identical — asserted by tests/test_hotloop.py.

    The MG_BDY face tagging of the legacy
    ``build_adjacency`` call is applied from the same sort records, so
    the ftag this function reads AND returns matches the legacy
    sequence's exactly.  Returns (mesh', fstar, t2_full, f2_full,
    cand_full)."""
    from .adjacency import face_sort, bdy_tags_from_sort
    from .edges import scatter_argmax2
    t, f, tp, fp, matched, valid_s = face_sort(mesh)
    mesh = bdy_tags_from_sort(mesh, t, f, matched, valid_s)
    own_s = matched & (t < tp) & (mesh.ftag[t, f] == 0) & \
        (mesh.ftag[tp, fp] == 0)
    q2 = q_tet[tp]
    is_star, _, _ = scatter_argmax2(t, -q2, -f, own_s, capT)
    site_star = jnp.where(is_star, t, capT)
    # ONE packed 3-column scatter for the winner fields (per-op overhead
    # dominates scatter cost on this device)
    pay = jnp.stack([f, tp, fp], axis=1)
    tbl = jnp.zeros((capT, 3), jnp.int32).at[site_star].set(
        pay, mode="drop", unique_indices=True)
    fstar, t2_full, f2_full = tbl[:, 0], tbl[:, 1], tbl[:, 2]
    cand_full = jnp.zeros(capT + 1, bool).at[
        jnp.where(own_s, t, capT)].max(own_s, mode="drop")[:capT]
    return mesh, fstar, t2_full, f2_full, cand_full


def swap23_wave(mesh: Mesh, met: jax.Array,
                budget_div: int = 8, budget: int | None = None,
                facesort: bool = False) -> SwapResult:
    """2-to-3 swap: interior faces whose two tets improve as an edge fan.

    Tets T1, T2 share interior face (p,q,r) with apexes a (in T1) and b (in
    T2); replaced by (a,b,p,q), (a,b,q,r), (a,b,r,p) — two slots reused,
    one allocated.

    ``facesort=True`` (PARMMG_SWAP_FACESORT): derive the face-pair table
    directly from the face-sort records (ops/adjacency.face_sort) instead
    of requiring a ``build_adjacency`` call between swap_edges_wave and
    this wave — the caller passes the post-edge-swap mesh as-is and
    the legacy rebuild's MG_BDY tagging is replayed from the same sort.  Bit-for-bit identical to the legacy sequence (see
    _pair_fields_facesort); ``adja`` is left stale, which is sound
    because this pairing is its only cycle-interior reader (the cycle
    exit contract rebuilds it).
    """
    capT, capP = mesh.capT, mesh.capP
    m6 = _met6(met)
    # per-tet quality once; ONE candidate face per tet — the face toward
    # the worst neighbor.  Then top-K compaction: only the K candidate
    # pairs with the WORST current quality go through the fan
    # construction / quality / routing / scatters (the same cost lever
    # as swap_edges_wave; claims resolve against the global tet pool so
    # exactness is unchanged, deferred candidates wait one wave)
    q_tet = quality_from_points(
        mesh.vert[mesh.tet], None if m6 is None else m6[mesh.tet])
    if facesort:
        mesh, fstar, t2_full, f2_full, cand_full = _pair_fields_facesort(
            mesh, q_tet, capT)
    else:
        fstar, t2_full, f2_full, cand_full = _pair_fields_adja(
            mesh, q_tet, capT)
    q_pair = jnp.minimum(q_tet, jnp.where(cand_full, q_tet[t2_full],
                                          jnp.inf))
    from .edges import wave_budget, topk_prep
    F = min(capT, wave_budget(capT, budget_div, budget))
    neg, ncand = topk_prep(cand_full, q_pair)
    defer = ncand > F
    _, sel = jax.lax.top_k(neg, F)
    ar = jnp.arange(F)
    t1 = sel.astype(jnp.int32)
    f1 = fstar[sel]
    t2 = t2_full[sel]
    f2 = f2_full[sel]
    cand = cand_full[sel]

    from ..core.constants import IDIR
    idir = jnp.asarray(IDIR)
    tv1 = mesh.tet[t1]                                   # [F,4]
    tv2 = mesh.tet[t2]
    pqr = tv1[ar[:, None], idir[f1]]                     # [F,3]
    a = tv1[ar, f1]                                      # apex in T1
    b = tv2[ar, f2]                                      # apex in T2

    p, q, r = pqr[:, 0], pqr[:, 1], pqr[:, 2]

    def mk(v0, v1, v2, v3):
        return jnp.stack([v0, v1, v2, v3], axis=1)

    # Face (p,q,r) = IDIR[f1] is oriented outward from T1 (away from a),
    # so for a visible pair the ring tets (x, y, a, b) over ring edges
    # (p,q), (q,r), (r,p) are all positively oriented; requiring all three
    # volumes strictly positive IS the convexity (visibility) test — no
    # sign fixing, which would mask invalid concave configurations.
    def signed_vol(tets):
        pts = mesh.vert[tets]
        d1 = pts[:, 1] - pts[:, 0]
        d2 = pts[:, 2] - pts[:, 0]
        d3 = pts[:, 3] - pts[:, 0]
        return jnp.sum(d1 * jnp.cross(d2, d3), -1)

    n1 = mk(p, q, a, b)
    n2 = mk(q, r, a, b)
    n3 = mk(r, p, a, b)
    pos = (signed_vol(n1) > EPSD) & (signed_vol(n2) > EPSD) & \
          (signed_vol(n3) > EPSD)
    # same region on both tets
    cand = cand & (mesh.tref[t1] == mesh.tref[t2])

    def qual(tets):
        pts = mesh.vert[tets]
        return quality_from_points(pts, None if m6 is None else m6[tets])

    # the 3 fan tets in ONE stacked call (per-op overhead dominates)
    q_old = jnp.minimum(q_tet[t1], q_tet[t2])
    q_fan = qual(jnp.concatenate([n1, n2, n3]))
    q_new = jnp.minimum(jnp.minimum(q_fan[:F], q_fan[F:2 * F]),
                        q_fan[2 * F:])
    cand = cand & pos & (q_new > jnp.maximum(SWAP_GAIN * q_old, QUAL_FLOOR))

    # --- claims on both tets (two-channel sort-free) ---------------------
    win = claim_shells(q_new - q_old, cand, (t1, t2), capT)
    # same-wave duplicate-edge veto: two winners whose fans both create
    # edge (a,b) (a "lens" of two face-pairs between the same apexes)
    # would put four tets on each (x,a,b) face; keep the first per key
    from .edges import sort_pairs as _sp23
    order_d, ka_d, _, first_d, _ = _sp23(
        jnp.minimum(a, b), jnp.maximum(a, b), win, capP)
    # a winner's sorted key is under INT32_MAX: no fetch of win
    dup_sorted = (ka_d != jnp.iinfo(jnp.int32).max) & ~first_d
    win = win & ~jnp.zeros(F, bool).at[order_d].set(
        dup_sorted, unique_indices=True)
    # slot-reusing allocation from the free pool (edges.free_rows):
    # rows freed by collapses are reclaimed instead of bumping the
    # watermark cursor
    from .edges import free_rows
    frow_t, nfree_t = free_rows(mesh.tmask, F)
    w_i = win.astype(jnp.int32)
    off = jnp.cumsum(w_i) - w_i
    fits = off < jnp.minimum(nfree_t, F)
    win = win & fits
    w_i = win.astype(jnp.int32)
    off = jnp.cumsum(w_i) - w_i
    t3 = frow_t[jnp.clip(off, 0, F - 1)]

    # --- tag routing: the fan tet over ring edge (x,y) inherits the two
    # exterior faces (x,y,a) [old T1, opposite the third ring vertex] and
    # (x,y,b) [old T2]; ring and spoke edges keep their old tags; the new
    # interior edge (a,b) and the two fan-internal faces are untagged.
    eof = jnp.asarray(_EDGE_OF)
    pos_p1 = idir[f1][:, 0]
    pos_q1 = idir[f1][:, 1]
    pos_r1 = idir[f1][:, 2]
    # batched position lookup of (p,q,r) in T2: one comparison + argmax
    eqm2 = tv2[:, None, :] == pqr[:, :, None]            # [F,3,4]
    P2x = jnp.argmax(eqm2, axis=2).astype(jnp.int32)     # [F,3]
    pos_p2, pos_q2, pos_r2 = P2x[:, 0], P2x[:, 1], P2x[:, 2]
    zero_u = jnp.zeros(F, jnp.uint32)
    zero_i = jnp.zeros(F, jnp.int32)

    def route_f(arr, pos_opp1, pos_opp2, zero):
        # new tet (x,y,a,b): col2 = (x,y,b) from T2, col3 = (x,y,a) from T1
        return jnp.stack([zero, zero,
                          arr[t2, pos_opp2], arr[t1, pos_opp1]], axis=1)

    def route_e(pos_x1, pos_y1, pos_x2, pos_y2):
        # (x,y,a,b) IARE edges: (xy, xa, xb, ya, yb, ab)
        return jnp.stack([
            mesh.etag[t1, eof[pos_x1, pos_y1]],
            mesh.etag[t1, eof[pos_x1, f1]],
            mesh.etag[t2, eof[pos_x2, f2]],
            mesh.etag[t1, eof[pos_y1, f1]],
            mesh.etag[t2, eof[pos_y2, f2]],
            zero_u], axis=1)

    ftag_n = [route_f(mesh.ftag, pos_r1, pos_r2, zero_u),
              route_f(mesh.ftag, pos_p1, pos_p2, zero_u),
              route_f(mesh.ftag, pos_q1, pos_q2, zero_u)]
    fref_n = [route_f(mesh.fref, pos_r1, pos_r2, zero_i),
              route_f(mesh.fref, pos_p1, pos_p2, zero_i),
              route_f(mesh.fref, pos_q1, pos_q2, zero_i)]
    etag_n = [route_e(pos_p1, pos_q1, pos_p2, pos_q2),
              route_e(pos_q1, pos_r1, pos_q2, pos_r2),
              route_e(pos_r1, pos_p1, pos_r2, pos_p2)]

    # one concatenated scatter per array (per-op overhead dominates)
    idx3 = jnp.concatenate([jnp.where(win, tt, capT) for tt in (t1, t2, t3)])
    tet = mesh.tet.at[idx3].set(
        jnp.concatenate([n1, n2, n3]), mode="drop")
    tmask = mesh.tmask.at[jnp.where(win, t3, capT)].set(True, mode="drop")
    tref3 = mesh.tref[t1]
    tref = mesh.tref.at[jnp.where(win, t3, capT)].set(tref3, mode="drop")
    ftag = mesh.ftag.at[idx3].set(jnp.concatenate(ftag_n), mode="drop")
    etag = mesh.etag.at[idx3].set(jnp.concatenate(etag_n), mode="drop")
    fref = mesh.fref.at[idx3].set(jnp.concatenate(fref_n), mode="drop")
    nsw = jnp.sum(w_i)
    nelem = jnp.maximum(mesh.nelem,
                        jnp.max(jnp.where(win, t3 + 1, 0)))
    out = dataclasses.replace(mesh, tet=tet, tmask=tmask, tref=tref,
                              ftag=ftag, etag=etag, fref=fref,
                              nelem=nelem.astype(jnp.int32))
    return SwapResult(out, nsw, defer)


