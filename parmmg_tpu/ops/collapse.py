"""Batched edge collapse — data-parallel replacement for Mmg's colver.

Reference behavior: short edges (metric length < 1/sqrt(2)) are removed by
merging one endpoint into the other; the shell tets die, the rest of the
removed vertex's ball is rewritten.  Constraints reproduced from Mmg's
``MMG5_colver`` checks + the ParMmg freeze contract (tag_pmmg.c:39-124):
required/corner/parallel vertices never move; boundary points only collapse
along boundary edges onto boundary points; ridge points only along ridges.

Independent-set scheduling (one wave):
  1. candidates = short, un-frozen edges; pick a *removed* endpoint per edge;
  2. per-vertex "top remover" priorities; geometric validity (positive
     volumes, no boundary fold-over, no overlong new edges) is evaluated for
     top removers only, tet-centrically;
  3. claims: a winner must be argmax at both endpoints and on every tet of
     the removed vertex's ball — so winner balls are disjoint and the
     per-candidate precheck stays exact under simultaneous application;
  4. apply via a vertex remap gather; shell tets (containing both endpoints)
     are invalidated; face tags of dying tets transfer to the surviving
     neighbor across (that face was interior, it becomes boundary iff it was
     tagged).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh
from ..core.constants import (
    IDIR, LSHRT, LLONG, EPSD, MG_BDY, MG_CRN, MG_GEO, MG_NOM, MG_REF,
    MG_REQ, MG_PARBDY, MG_PARBDYBDY, QUAL_FLOOR)
from .edges import (unique_edges, edge_lengths, claim_channels,
                    scatter_argmax2, NEG_INF, PRI_MIN)
from . import rowpack

_IDIR_J = jnp.asarray(IDIR)


# the simulated quality of a ball row whose collapse would leave a
# surface face farther than hausd from the surface, and is sound
# otherwise: below every quality, above the -inf of an invalid row
DEEP_FACE = -1e30


class CollapseResult(NamedTuple):
    mesh: Mesh
    ncollapse: jax.Array
    # did any dying tet donate face/edge tags (surface rewired)?  False
    # lets the caller skip the boundary re-propagation pass entirely
    surface_changed: jax.Array = None
    deferred: jax.Array = None  # scalar bool: candidates exceeded the
    #                 top-K budget; they wait for the next wave
    nhveto: jax.Array = None  # scalar int32: candidates the hausd test
    #                 refused (boundary edges whose surface would move by
    #                 more than hausd, and collapses that would leave a
    #                 surface face farther than hausd from the surface);
    #                 0 without hausd


def _removable(vtag, other_vtag, edge_tag):
    """May vertex v (tags vtag) be deleted by collapsing along this edge?"""
    free = (vtag & (MG_REQ | MG_CRN | MG_PARBDY | MG_NOM)) == 0
    on_bdy = (vtag & MG_BDY) != 0
    # the target is a TRUE boundary vertex: a seam vertex carries MG_BDY
    # with its freeze (PARBDY_TAGS) whether or not it lies on the
    # surface, and is true boundary only with MG_PARBDYBDY.  A bare
    # MG_BDY that a pass left on one slot of an interior edge otherwise
    # takes a surface vertex onto an interior seam vertex (PERF.md
    # section 6, PR 37: a cube's face pulled into the volume)
    other_bdy = ((other_vtag & MG_BDY) != 0) & (
        ((other_vtag & MG_PARBDY) == 0) |
        ((other_vtag & MG_PARBDYBDY) != 0))
    bdy_ok = ~on_bdy | (((edge_tag & MG_BDY) != 0) & other_bdy)
    on_geo = (vtag & MG_GEO) != 0
    # a ridge point may slide along its ridge onto another ridge point or
    # onto the corner terminating the ridge (Mmg chkcol_bdy semantics)
    geo_ok = ~on_geo | (((edge_tag & MG_GEO) != 0) &
                        ((other_vtag & (MG_GEO | MG_CRN)) != 0))
    # likewise a reference-edge point stays on its reference line
    on_ref = (vtag & MG_REF) != 0
    ref_ok = ~on_ref | (((edge_tag & MG_REF) != 0) &
                        ((other_vtag & (MG_REF | MG_CRN)) != 0))
    return free & bdy_ok & geo_ok & ref_ok


def collapse_wave(mesh: Mesh, met: jax.Array, lmin: float = LSHRT,
                  lmax: float = LLONG,
                  sliver_q: float | None = None,
                  hausd: float | None = None,
                  budget_div: int = 8, budget: int | None = None,
                  et=None, lens=None,
                  stale_tets: jax.Array | None = None,
                  vtan: jax.Array | None = None,
                  vn: jax.Array | None = None,
                  q_tet: jax.Array | None = None) -> CollapseResult:
    """One independent-set collapse wave.

    Normal mode: contract edges shorter than ``lmin`` (Mmg's colver over
    the short-edge cascade).  Sliver mode (``sliver_q`` set): target the
    edges of tets whose quality is below ``sliver_q`` regardless of
    length, and additionally require that the simulated collapse STRICTLY
    improves the min quality over the removed vertex's ball — the batched
    analogue of Mmg's bad-element optimization pass (``MMG3D_opttyp``
    collapses on ``MMG3D_BADKAL`` elements).

    ``et``/``lens``/``stale_tets``: shared-table mode.  adapt_cycle_impl
    builds ONE edge table + lengths before the split wave and passes
    them to both ops; ``stale_tets`` is the split's modification
    footprint, and any candidate edge touching a vertex of a modified
    tet is deferred to the next wave (its table row describes pre-split
    geometry).  Validity/quality below run against the CURRENT (post-
    split) mesh arrays, which are identical on every unmodified slot.

    ``q_tet``: sliver mode only, the per-tet quality of ``mesh`` in
    ``met`` where the caller has it already (sliver_polish_impl computes
    it to decide whether the wave has an input at all).
    """
    capT, capP = mesh.capT, mesh.capP
    if et is None:
        et = unique_edges(mesh)
    if lens is None:
        lens = edge_lengths(mesh, et, met)
    Efull = et.ev.shape[0]
    va_f = jnp.clip(et.ev[:, 0], 0, capP - 1)
    vb_f = jnp.clip(et.ev[:, 1], 0, capP - 1)

    # what the candidacy reads of an endpoint rides in ONE row a vertex
    # (ops/rowpack: a row gather costs a third of one scalar's): the tag
    # word, the staleness flag and, under hausd, the coordinates, the
    # normal and the line tangent
    stale = sliver_q is None and stale_tets is not None
    cols = {"tag": mesh.vtag}
    if stale:
        # staleness veto: vertices of any tet the split modified
        cols["stale"] = jnp.zeros(capP + 1, bool).at[
            jnp.where(stale_tets[:, None], mesh.tet, capP)
            .reshape(-1)].max(
            jnp.repeat(stale_tets, 4), mode="drop")[:capP]
    if hausd is not None:
        from .analysis import boundary_vertex_normals, \
            ridge_vertex_tangents
        if vn is None:
            vn = boundary_vertex_normals(mesh)
        tanv = vtan if vtan is not None \
            else ridge_vertex_tangents(mesh, et=et)
        cols.update(p=mesh.vert, n=vn, tan=tanv)
    ends = rowpack.pack(**cols)
    end_a, end_b = ends.take(va_f), ends.take(vb_f)

    frozen_edge = (et.etag & (MG_REQ | MG_PARBDY)) != 0
    if sliver_q is None:
        short = et.emask & (lens < lmin) & ~frozen_edge
        if stale:
            short = short & ~end_a["stale"] & ~end_b["stale"]
    else:
        if q_tet is None:
            from .quality import quality_from_points
            q_tet = quality_from_points(
                mesh.vert[mesh.tet],
                None if met.ndim == 1 else met[mesh.tet])
        bad_tet = mesh.tmask & (q_tet < sliver_q)
        bad_edge = jnp.zeros(et.ev.shape[0], bool).at[
            et.edge_id.reshape(-1)].max(
            jnp.repeat(bad_tet, 6), mode="drop")
        # don't lengthen already-long edges by contracting into them
        short = et.emask & bad_edge & ~frozen_edge & (lens < lmax)

    ta_f, tb_f = end_a["tag"], end_b["tag"]
    rem_b_f = _removable(tb_f, ta_f, et.etag)   # can delete b (keep a)
    rem_a_f = _removable(ta_f, tb_f, et.etag)
    pre = short & (rem_a_f | rem_b_f)

    if hausd is not None:
        # surface-approximation veto (Mmg -hausd) at FULL width, BEFORE
        # the top-K cut: a post-cut veto would let permanently-vetoed
        # boundary edges pin budget slots every wave, starving legal
        # candidates ranked past K
        na_f, nb_f = end_a["n"], end_b["n"]
        tana_f, tanb_f = end_a["tan"], end_b["tan"]
        on_bdy_f = (et.etag & MG_BDY) != 0
        d_f = end_b["p"] - end_a["p"]
        t_a = d_f - na_f * jnp.sum(na_f * d_f, -1, keepdims=True)
        t_b = d_f - nb_f * jnp.sum(nb_f * d_f, -1, keepdims=True)
        dev = jnp.linalg.norm(0.125 * (t_a - t_b), axis=-1)
        # feature-line edges: curvature deviation along the LINE
        # tangent, not the (multivalued) surface normal — matches the
        # tangent-circle lift in split_wave
        on_line_f = (et.etag & (MG_GEO | MG_REF)) != 0
        ta_l = tana_f * jnp.sum(tana_f * d_f, -1, keepdims=True)
        tb_l = tanb_f * jnp.sum(tanb_f * d_f, -1, keepdims=True)
        dev_l = jnp.linalg.norm(0.125 * (ta_l - tb_l), axis=-1)
        dev = jnp.where(on_line_f, dev_l, dev)
        hveto = pre & on_bdy_f & (dev > hausd)
        nhveto = jnp.sum(hveto, dtype=jnp.int32)
        pre = pre & ~hveto
    else:
        nhveto = jnp.zeros((), jnp.int32)

    # Everything below (top-K sort, role derivation, tet-centric
    # validity, claims, apply) is lax.cond-skipped when NO candidate
    # exists — at convergence the wave then costs only the table +
    # candidacy masks.
    def _idle(_):
        return CollapseResult(mesh, jnp.zeros((), jnp.int32),
                              jnp.zeros((), bool), jnp.zeros((), bool),
                              nhveto)

    def _act(_):
        # top-K compaction (the wave's cost lever, PERF.md section 5): the K highest-
        # priority candidates go through the heavy machinery; claims stay
        # exact (they resolve against global vertex/tet pools) and deferred
        # candidates are picked up by the next wave.  Priority: shortest
        # edges in sizing mode; WORST incident tet in sliver mode (the pass
        # exists to raise the min — edge length would misrank the targets)
        from .edges import wave_budget, topk_prep
        K = min(Efull, wave_budget(capT, budget_div, budget))
        if sliver_q is None:
            prio = lens
        else:
            eq_min = jnp.full(Efull, jnp.inf).at[
                et.edge_id.reshape(-1)].min(
                jnp.repeat(jnp.where(bad_tet, q_tet, jnp.inf), 6),
                mode="drop")
            prio = eq_min
        # fused scoring prep + top-K by priority (smallest first) without
        # a full-width argsort
        neg, npre = topk_prep(pre, prio)
        defer = npre > K
        _, sel = jax.lax.top_k(neg, K)
        lens_c = lens[sel]
        va = va_f[sel]
        vb = vb_f[sel]
        cand = pre[sel]
        del_b = rem_b_f[sel]
        rm = jnp.where(del_b, vb, va)
        kp = jnp.where(del_b, va, vb)

        # sort-free claim priority: (s, t) = (-length, unique hash); shorter
        # edge = higher score, ties broken without spatial bias
        s, t = claim_channels(-lens_c, cand)
        # per-vertex top remover and its kept endpoint; v_s/v_t are the
        # per-vertex channel maxima (the sortless 'rmpri')
        is_top, v_s, v_t = scatter_argmax2(rm, s, t, cand, capP)
        kept_of = jnp.zeros(capP, jnp.int32).at[
            jnp.where(is_top, rm, capP)].set(kp, mode="drop",
                                             unique_indices=True)

        # --- claims + validity, claimed-corner only --------------------------
        # tet claim = (s,t)-max removal target over the 4 corners.  A
        # remover contested at ANY ball tet (some corner holds a target
        # that is not that tet's claim max) can never win, so geometric
        # validity and the simulated ball quality only need evaluating at
        # each tet's single CLAIMED corner — [T]-width instead of the old
        # [4T] stacked variants, with the contested/invalid cases folded
        # into the same ball-quality scatter as -inf rows
        # (validity+ballq was ~28 ms; a block by phase: PERF.md section 5).
        # a corner reads its coordinates, its two claim maxima and, where
        # the stage wants them, its size, its normal and whether it is
        # singular in ONE row (ops/rowpack)
        tv = mesh.tet                                          # [T,4]
        cols = {"p": mesh.vert, "s": v_s[:capP], "t": v_t[:capP]}
        if met.ndim == 1:
            cols["h"] = met
        if hausd is not None:
            cols.update(n=vn, sing=(mesh.vtag & (
                MG_GEO | MG_CRN | MG_REF | MG_NOM)) != 0)
        at_vertex = rowpack.pack(**cols)
        corner = at_vertex.take(tv)
        vpos = corner["p"]                                     # [T,4,3]
        vs_c = corner["s"]                                     # [T,4] score max
        vt_c = corner["t"]                                     # [T,4] tie max
        has_c = jnp.isfinite(vs_c)        # corner is a top-removal target
        tmax_s = jnp.max(jnp.where(mesh.tmask[:, None], vs_c, NEG_INF), axis=1)
        selc = (vs_c == tmax_s[:, None]) & jnp.isfinite(tmax_s)[:, None]
        tsel = jnp.where(selc, vt_c, PRI_MIN)
        tmax_t = jnp.max(tsel, axis=1)
        corner_max = selc & (tsel == tmax_t[:, None])
        claimed = corner_max & has_c                           # [T,4]
        has_cl = jnp.any(claimed, axis=1) & mesh.tmask
        kc = jnp.argmax(claimed, axis=1)                       # [T]
        ar0 = jnp.arange(capT)
        rm_v = tv[ar0, kc]                                     # claimed target
        # its kept vertex, and that vertex's own row, composed a vertex
        # ([capP] wide) so that a tet fetches both in one row
        of_kept = at_vertex.take(jnp.clip(kept_of, 0, capP - 1))
        del of_kept["s"], of_kept["t"]
        kept = rowpack.pack(v=kept_of, **of_kept).take(
            jnp.clip(rm_v, 0, capP - 1))
        kept_v, kept_p = kept["v"], kept["p"]                  # [T], [T,3]
        # does this tet also contain the kept vertex? then it dies with the
        # collapse — it drops out of the surviving ball, no checks needed
        contains_kept = jnp.zeros(capT, bool)
        for j in range(4):
            contains_kept = contains_kept | \
                ((tv[:, j] == kept_v) & (j != kc))
        active_cl = has_cl & ~contains_kept

        # single simulated variant per tet: claimed corner -> kept position
        oh = jnp.arange(4)[None, :] == kc[:, None]             # [T,4]
        p = jnp.where(oh[..., None], kept_p[:, None, :], vpos)
        d1 = p[:, 1] - p[:, 0]
        d2 = p[:, 2] - p[:, 0]
        d3 = p[:, 3] - p[:, 0]
        vol = jnp.einsum("ti,ti->t", d1, jnp.cross(d2, d3)) / 6.0
        bad = vol <= EPSD
        if hausd is not None:
            from .analysis import face_depth
            # the simulated tet's corner normals, and which corners are
            # singular (ridge, corner, reference line: no one normal)
            nrm_c = jnp.where(oh[..., None], kept["n"][:, None, :],
                              corner["n"])                     # [T,4,3]
            sing_c = jnp.where(oh, kept["sing"][:, None], corner["sing"])
            deep = jnp.zeros(capT, bool)
        # fold-over: boundary faces containing the claimed corner must
        # keep their orientation
        for f in range(4):
            idx = IDIR[f]
            n_old = jnp.cross(vpos[:, idx[1]] - vpos[:, idx[0]],
                              vpos[:, idx[2]] - vpos[:, idx[0]])
            n_new = jnp.cross(p[:, idx[1]] - p[:, idx[0]],
                              p[:, idx[2]] - p[:, idx[0]])
            isb = (mesh.ftag[:, f] & MG_BDY) != 0
            flip = jnp.sum(n_old * n_new, -1) <= 0
            bad = bad | (isb & flip & (kc != f))
            if hausd is not None:
                # the surface face this collapse LEAVES (the removed
                # corner at the kept vertex): its centroid and its two
                # new edges stand within hausd of the surface its
                # corners' normals describe.  The test of the removed
                # edge above cannot see them: they are up to twice as
                # long.  A singular corner takes the face's own normal,
                # so a flat face reads 0 whatever bounds it
                nf = n_new / (jnp.linalg.norm(
                    n_new, axis=-1, keepdims=True) + EPSD)
                nrm = jnp.where(sing_c[..., None], nf[:, None, :], nrm_c)
                at_kept = oh[:, list(idx)]                     # [T,3]
                touches = jnp.stack(
                    [at_kept[:, 0] | at_kept[:, 1],
                     at_kept[:, 1] | at_kept[:, 2],
                     at_kept[:, 0] | at_kept[:, 2]], axis=-1)
                deep = deep | (isb & (kc != f) & (face_depth(
                    p[:, list(idx)], nrm[:, list(idx)], touches) > hausd))
        # overlong new edges from the kept vertex to the other corners
        if met.ndim == 1:
            from .quality import edge_length_iso
            for j in range(4):
                lnew = edge_length_iso(kept_p, p[:, j], kept["h"],
                                       corner["h"][:, j])
                bad = bad | ((lnew > lmax) & (kc != j))

        # --- ball-quality gate ----------------------------------------------
        # Normal mode: the collapse must not degrade the ball min quality
        # below 30% of its old value nor below the degeneracy floor
        # (MMG5_colver's calnew/calold check).  Sliver mode: STRICT
        # improvement.  Invalid geometry and contested balls force -inf.
        from .quality import quality_from_points
        mq = None if met.ndim == 1 else met[tv]
        # q_tet is a closure variable in sliver mode — don't shadow it
        q_ball = quality_from_points(vpos, mq) if sliver_q is None \
            else q_tet
        idx4c = jnp.concatenate(
            [jnp.where(mesh.tmask, tv[:, k], capP) for k in range(4)])
        ballq_old = jnp.full(capP + 1, jnp.inf).at[idx4c].min(
            jnp.tile(jnp.where(mesh.tmask, q_ball, jnp.inf), 4),
            mode="drop")
        mq_cl = None if mq is None else jnp.where(
            oh[..., None], met[jnp.clip(kept_v, 0, capP - 1)][:, None, :],
            mq)
        qv = quality_from_points(p, mq_cl)                     # [T]
        row_val = jnp.where(bad, -jnp.inf, qv)
        if hausd is not None:
            # refused for the surface and for nothing else: a value of
            # its own, so that the ball's minimum says which it was
            row_val = jnp.where(deep & ~bad, DEEP_FACE, row_val)
        # contested rows: a corner holding a target that is NOT the tet's
        # claim max kills that target via a -inf contribution
        mism4 = jnp.concatenate(
            [has_c[:, k] & ~corner_max[:, k] & mesh.tmask for k in range(4)])
        idx_cat = jnp.concatenate(
            [jnp.where(active_cl, rm_v, capP),
             jnp.where(mism4, jnp.concatenate([tv[:, k] for k in range(4)]),
                       capP)])
        val_cat = jnp.concatenate(
            [jnp.where(active_cl, row_val, jnp.inf),
             jnp.where(mism4, -jnp.inf, jnp.inf)])
        ballq_new = jnp.full(capP + 1, jnp.inf).at[idx_cat].min(
            val_cat, mode="drop")
        if sliver_q is None:
            ok = (ballq_new[:capP] >= 0.3 * ballq_old[:capP]) & \
                 (ballq_new[:capP] > QUAL_FLOOR)
            geombad = ~ok
        else:
            improves = ballq_new[:capP] > ballq_old[:capP]
            geombad = ~improves

        # vertex claims: a winner must be the (s,t)-max among all candidate
        # edges touching either of its endpoints (both roles) — one
        # concatenated scatter per channel
        idx_rk = jnp.concatenate([jnp.where(cand, rm, capP),
                                  jnp.where(cand, kp, capP)])
        cl_s = jnp.full(capP + 1, NEG_INF).at[idx_rk].max(
            jnp.tile(s, 2), mode="drop")
        eq_rm = cand & (s == cl_s[rm])
        eq_kp = cand & (s == cl_s[kp])
        idx_rk2 = jnp.concatenate([jnp.where(eq_rm, rm, capP),
                                   jnp.where(eq_kp, kp, capP)])
        cl_t = jnp.full(capP + 1, PRI_MIN).at[idx_rk2].max(
            jnp.tile(t, 2), mode="drop")
        claim_ok = eq_rm & (t == cl_t[rm]) & eq_kp & (t == cl_t[kp])

        # contested balls are already folded into geombad via -inf rows
        win = cand & is_top & ~geombad[rm] & claim_ok
        ncol = jnp.sum(win.astype(jnp.int32))
        nveto = nhveto if hausd is None else nhveto + jnp.sum(
            cand & is_top & claim_ok & (ballq_new[rm] == DEEP_FACE),
            dtype=jnp.int32)

        # --- apply: vertex remap + dead shell tets ---------------------------
        # the whole apply phase (remap gather, dup detection, keyed tag
        # joins — 3 full-width sorts) is lax.cond-skipped when the wave has
        # no winner: near convergence most waves are empty and the apply
        # cost would dominate the cycle for nothing
        def _apply_collapse(_):
            return _collapse_apply(mesh, met, win, rm, kp, capT, capP)

        def _skip_collapse(_):
            return (mesh.tet, mesh.tmask, mesh.vmask, mesh.ftag, mesh.fref,
                    mesh.etag, jnp.zeros((), bool))

        new_tet, tmask, vmask, ftag, fref, etag, schg = jax.lax.cond(
            ncol > 0, _apply_collapse, _skip_collapse, None)

        out = dataclasses.replace(
            mesh, tet=new_tet, tmask=tmask, vmask=vmask, ftag=ftag,
            fref=fref, etag=etag)
        return CollapseResult(out, ncol, schg, defer, nveto)

    return jax.lax.cond(jnp.any(pre), _act, _idle, None)


def _collapse_apply(mesh: Mesh, met, win, rm, kp, capT, capP):
    """Apply phase of collapse_wave (see there): vertex remap, dead-tet
    detection, and the donor tag/ref keyed joins."""
    remap = jnp.arange(capP, dtype=jnp.int32)
    remap = remap.at[jnp.where(win, rm, capP)].set(
        kp, mode="drop", unique_indices=True)   # winners exclusive at rm
    new_tet = rowpack.take(remap, mesh.tet)     # remap[tet], by rows
    # dead = any duplicated vertex pair (tet contained rm and kp)
    dup = jnp.zeros(capT, bool)
    for i in range(4):
        for j in range(i + 1, 4):
            dup = dup | (new_tet[:, i] == new_tet[:, j])
    dead = dup & mesh.tmask
    tmask = mesh.tmask & ~dead
    vmask = mesh.vmask.at[jnp.where(win, rm, capP)].set(False, mode="drop")

    # Donor joins are themselves cond-skipped when no dying tet carries
    # any face/edge tag or face ref — interior collapses (the bulk of a
    # sizing run) then skip all 3 join sorts.
    has_donor_info = jnp.any(
        dead[:, None] & ((mesh.ftag != 0) | (mesh.fref != 0))) | \
        jnp.any(jnp.repeat(dead, 6) & (mesh.etag.reshape(-1) != 0))

    def _joins(_):
        return _collapse_tag_joins(mesh, new_tet, dead, tmask, capT, capP)

    def _no_joins(_):
        return mesh.ftag, mesh.fref, mesh.etag

    ftag, fref, etag = jax.lax.cond(has_donor_info, _joins, _no_joins,
                                    None)
    return new_tet, tmask, vmask, ftag, fref, etag, has_donor_info


def _tag_joins_core(new_tet, ftag, fref, etag, donor, recv, capP):
    """Width-generic body of the donor tag/ref keyed joins.

    Runs over n = new_tet.shape[0] tet rows (the FULL capT width or a
    compacted donor band — see ``_collapse_tag_joins``) and returns the
    ADD arrays only: ``(add_tag [n,4] uint32, add_ref [n,4] int32,
    add_e [n,6] uint32)``.  Rows with neither donor nor recv set are
    keyed with the int32-max sentinel and contribute/receive nothing.
    Segment aggregation is OR/max — commutative and associative — so the
    adds per row are independent of the sort width n: a band containing
    every donor and every key-matching receiver produces bit-identical
    adds to the full-width join.
    """
    n = new_tet.shape[0]
    # --- transfer face tags/refs from dying tets: keyed face join --------
    # Every face of the REMAPPED mesh is keyed by its sorted vertex
    # triple; dying tets donate their old tags/refs, alive slots with the
    # same key OR/max them in.  This covers BOTH transfer cases: the
    # shared-slot case (dying tet's interior face survives on the
    # neighbor — the old adja-based transfer) and the remapped-boundary
    # case (dying tet's tagged surface face (rm,u,w) becomes (kp,u,w),
    # owned by a tet that never shared a slot with the donor — the old
    # code recovered only the MG_BDY bit via the next build_adjacency and
    # silently dropped fref/REQ/REF bits).
    from ..core.mesh import tet_face_vertices
    from .edges import (PACK_LIMIT, segment_first, segmented_or,
                        segmented_max, sort_carry)
    F4 = n * 4
    fvn = jnp.sort(tet_face_vertices(new_tet).reshape(F4, 3), axis=1)
    donor_f = jnp.repeat(donor, 4)
    recv_f = jnp.repeat(recv, 4)
    rel_f = donor_f | recv_f
    i32max = jnp.iinfo(jnp.int32).max
    # the donors' tags and refs ride in the sort (edges.sort_carry): no
    # stage here fetches through the permutation it has just made
    dtag_in = jnp.where(donor_f, ftag.reshape(F4), 0)
    dref_in = jnp.where(donor_f, fref.reshape(F4), 0)
    if capP <= PACK_LIMIT:
        keys_f = (jnp.where(rel_f, fvn[:, 0], i32max),
                  jnp.where(rel_f, fvn[:, 1] * capP + fvn[:, 2], i32max))
    else:
        keys_f = tuple(jnp.where(rel_f, fvn[:, j], i32max)
                       for j in range(3))
    order_f, ks_f, (dtag_f, dref_f) = sort_carry(keys_f,
                                                 (dtag_in, dref_in))
    first_f = segment_first(ks_f)
    seg_f = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first_f, jnp.arange(F4), 0))
    is_last_f = jnp.concatenate([first_f[1:], jnp.array([True])])
    or_f = segmented_or(first_f, dtag_f)
    tot_tag = jnp.zeros(F4, jnp.uint32).at[
        jnp.where(is_last_f, seg_f, F4)].set(
        or_f, mode="drop", unique_indices=True)
    add_tag_s = tot_tag[seg_f]
    add_tag = jnp.zeros(F4, jnp.uint32).at[order_f].set(
        add_tag_s, unique_indices=True).reshape(n, 4)
    mx_f = segmented_max(first_f, dref_f)
    tot_ref = jnp.zeros(F4, jnp.int32).at[
        jnp.where(is_last_f, seg_f, F4)].set(
        mx_f, mode="drop", unique_indices=True)
    add_ref = jnp.zeros(F4, jnp.int32).at[order_f].set(
        tot_ref[seg_f], unique_indices=True).reshape(n, 4)

    # --- transfer edge tags from dying tets to surviving slots -----------
    # The collapse merges edge (u,rm) into (u,kp).  Mmg's colver unites
    # the tags of the merged edges; without this, a ridge edge loses its
    # MG_GEO when every tet carrying the tagged slot dies (all its shell
    # tets contain rm AND kp) — the untagged ridge then erodes (volume
    # loss).  Batched equivalent: a keyed OR-join — sort ALL remapped
    # slot keys (surviving slots as receivers, dying tets' slots as
    # donors of their OLD tag) and OR each key group's donor tags into
    # its receivers.
    from ..core.mesh import tet_edge_vertices
    from .edges import sort_pairs
    ev_new = tet_edge_vertices(new_tet).reshape(n * 6, 2)
    ka = jnp.minimum(ev_new[:, 0], ev_new[:, 1])
    kb = jnp.maximum(ev_new[:, 0], ev_new[:, 1])
    alive_s = jnp.repeat(recv, 6)
    donor_s = jnp.repeat(donor, 6)
    rel = alive_s | donor_s
    order, _, _, first, (dtag,) = sort_pairs(
        ka, kb, rel, capP, (jnp.where(donor_s, etag.reshape(n * 6), 0),))
    seg = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first, jnp.arange(n * 6), 0))
    # segment OR of donor tags, then broadcast the segment total back to
    # every member (the OR-scan total sits at the LAST member)
    or_fwd = segmented_or(first, dtag)
    is_last = jnp.concatenate([first[1:], jnp.array([True])])
    # per-segment total, scattered to the head slot then gathered by seg
    # id; buffer sized n6 exactly so the masked-out sentinel index n6 is
    # genuinely out of bounds (dropped) — required for unique_indices
    total_at_head = jnp.zeros(n * 6, jnp.uint32).at[
        jnp.where(is_last, seg, n * 6)].set(
        or_fwd, mode="drop", unique_indices=True)
    add_sorted = total_at_head[seg]                       # [capE] per slot
    add_e = jnp.zeros(n * 6, jnp.uint32).at[order].set(
        add_sorted, unique_indices=True).reshape(n, 6)
    return add_tag, add_ref, add_e


def collapse_band_width(capT: int) -> int:
    """Static donor-band width for ``_collapse_tag_joins``: geo-bucketed
    (utils/compilecache.bucket — the existing shape ladder, so no new
    shape families) from capT//4, never exceeding capT."""
    from ..utils.compilecache import bucket
    return bucket(max(1, capT // 4), floor=256, scheme="geo", cap=capT)


def _collapse_tag_joins(mesh: Mesh, new_tet, dead, tmask, capT, capP):
    """Keyed face/edge tag-transfer joins (see collapse_wave docstring).

    PARMMG_COLLAPSE_BAND (default on): a steady-state wave kills ~30
    tets, yet the joins sort 4*capT face keys and 6*capT edge keys.  The
    banded path compacts the join to the DONOR BAND — the dead tets plus
    every live tet containing a "relevant vertex" (a vertex of a
    remapped dead tet) — and scatters the adds back.

    Coverage proof (band result ≡ full result, bit for bit): every donor
    key (face/edge of a remapped dead tet) has all its endpoints among
    the relevant vertices, so any LIVE row matching a donor key contains
    ≥2 relevant vertices and is in the band by construction; every
    non-band row therefore lands in a segment with no donor and gets
    add = 0 in the full-width join — exactly the zeros the band scatter
    leaves behind.  Degenerate donor keys (the collapsed (kp,kp,·)
    faces/edges of a dead tet) can never match a live row, whose
    remapped vertices stay distinct.  Aggregation is OR/max, so segment
    results are independent of the sort width (see _tag_joins_core).
    The band width is static (collapse_band_width); when the band
    overflows it — a mass-collapse wave — a lax.cond falls back to the
    full-width join, which computes the identical result, so the switch
    itself is parity-safe.
    """
    import os

    def _merge(add_tag, add_ref, add_e):
        ftag = jnp.where(tmask[:, None], mesh.ftag | add_tag, mesh.ftag)
        fref = jnp.where(tmask[:, None] & (mesh.fref == 0) & (add_ref != 0),
                         add_ref, mesh.fref)
        etag = jnp.where(tmask[:, None], mesh.etag | add_e, mesh.etag)
        return ftag, fref, etag

    B = collapse_band_width(capT) \
        if os.environ.get("PARMMG_COLLAPSE_BAND", "") != "0" else capT
    if B >= capT:  # tiny meshes: the band ladder reaches capT anyway
        return _merge(*_tag_joins_core(
            new_tet, mesh.ftag, mesh.fref, mesh.etag, dead, tmask, capP))

    # relevant vertices: every vertex of a remapped dead tet
    rv = jnp.zeros(capP + 1, bool).at[
        jnp.where(dead[:, None], new_tet, capP).reshape(-1)].max(
        jnp.repeat(dead, 4), mode="drop")[:capP]
    band = dead | (tmask & jnp.any(rowpack.take(rv, new_tet), axis=1))
    nband = jnp.sum(band.astype(jnp.int32))

    def _banded(_):
        rows = jnp.nonzero(band, size=B, fill_value=capT)[0]
        vrow = rows < capT
        rc = jnp.clip(rows, 0, capT - 1)
        bt, br, be = _tag_joins_core(
            new_tet[rc], mesh.ftag[rc], mesh.fref[rc], mesh.etag[rc],
            dead[rc] & vrow, tmask[rc] & vrow, capP)
        add_tag = jnp.zeros((capT, 4), jnp.uint32).at[rows].set(
            bt, mode="drop", unique_indices=True)
        add_ref = jnp.zeros((capT, 4), jnp.int32).at[rows].set(
            br, mode="drop", unique_indices=True)
        add_e = jnp.zeros((capT, 6), jnp.uint32).at[rows].set(
            be, mode="drop", unique_indices=True)
        return add_tag, add_ref, add_e

    def _full(_):
        return _tag_joins_core(new_tet, mesh.ftag, mesh.fref, mesh.etag,
                               dead, tmask, capP)

    return _merge(*jax.lax.cond(nband <= B, _banded, _full, None))
