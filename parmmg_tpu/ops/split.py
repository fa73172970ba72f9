"""Batched edge split — data-parallel replacement for Mmg's split cascade.

Reference behavior being reproduced: inside ``MMG5_mmg3d1_delone`` (called by
the group loop at /root/reference/src/libparmmg1.c:737-739) long edges
(metric length > sqrt(2)) are split by inserting a point, and every tet of
the edge's shell is cut in two; entities tagged ``MG_REQ`` (in particular the
frozen parallel interface, tag_pmmg.c:39-124) must not be touched.

TPU design: instead of a sequential cascade, each *wave* selects a maximal
independent set of splittable edges (no two in the same tet) and applies all
of them at once:

1.  every tet nominates its longest splittable edge;
2.  an edge wins iff **all** tets of its shell nominated it (so the whole
    shell splits coherently and each tet is modified by at most one split);
3.  winning edges allocate midpoints (prefix-sum slot assignment) and each
    shell tet is cut in two, tags inherited per the local topology tables.

Determinism: priorities are unique int32 ranks, so the independent set — and
hence the output mesh — is a pure function of the input.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mesh import Mesh
from ..core.constants import (
    IARE, EDGE_FACES, FACE_EDGES, IDIR, LLONG, MG_BDY, MG_GEO, MG_REQ,
    MG_PARBDY, MG_REF)
from .edges import (EdgeTable, unique_edges, edge_lengths, claim_channels,
                    NEG_INF, PRI_MIN)
from .rowpack import pack

_IARE_J = jnp.asarray(IARE)


class SplitResult(NamedTuple):
    mesh: Mesh
    met: jax.Array
    nsplit: jax.Array      # scalar int32: number of edges split
    overflow: jax.Array    # scalar bool: capacity exhausted, wave truncated
    modified: jax.Array = None  # [capT] bool: tets rewritten/created this
    #                 wave (consumed by collapse_wave's staleness veto
    #                 when both ops share one pre-split edge table)
    deferred: jax.Array = None  # scalar bool: viable winners were dropped
    #                 by the top-K / shell budgets (NOT by gates or
    #                 capacity); they wait for the next wave
    nbdy: jax.Array = None  # scalar int32: of ``nsplit``, the splits of
    #                 boundary edges (their midpoints are surface points:
    #                 the ones the hausd lift places)


def _interp_met_mid(met, va, vb):
    """Metric at an edge midpoint (linear interpolation of the metric
    coefficients; MMG5_intmet semantics simplified to P1)."""
    return 0.5 * (met[va] + met[vb])


def split_wave(mesh: Mesh, met: jax.Array, lmax: float = LLONG,
               frozen_vtag: int = MG_REQ | MG_PARBDY,
               hausd: float | None = None,
               budget_div: int = 8,
               fem_only: bool = False,
               et: EdgeTable | None = None,
               lens: jax.Array | None = None,
               vtan: jax.Array | None = None,
               vn: jax.Array | None = None,
               prescreen: bool = True) -> SplitResult:
    """One independent-set split wave. Jittable; static shapes throughout.

    ``hausd`` enables the PLACEMENT half of surface-approximation
    control (Mmg -hausd): refinement pressure itself comes from the
    metric (driver.build_metric folds sqrt(8*hausd/kappa) into boundary
    sizes via ops.metric.hausd_metric_bound — the defsiz route), while
    here regular boundary midpoints are LIFTED onto the cubic Bezier
    curve
    through the endpoints+normals (MMG5_BezierRegular flavor) — the
    deviation estimate is |t_a - t_b|/8 with t_* the edge vector
    projected on each endpoint's tangent plane; the midpoint correction
    is (t_a - t_b)/8, exact to O(h^4) on a sphere.  Ridge/corner/required
    endpoints are excluded (their normals are multivalued — the flat
    cube workloads are bit-for-bit unchanged); a frozen seam vertex
    counts as regular where the mesh carries its whole-fan normal.

    ``fem_only``: instead of long edges, target INTERIOR edges whose two
    endpoints both lie on the boundary — the FEM-incompatible
    configuration (an element can end up with all four vertices, or two
    faces, on the boundary).  Splitting such an edge inserts an interior
    point, which is exactly Mmg's fem-mode topology fix; the reference
    forwards ``info.fem`` (default on, API_functions_pmmg.c:413,652) to
    Mmg per group.

    ``budget_div`` widens/narrows the per-wave winner budget (the shared
    ops/edges.wave_budget formula; winners past it are deferred to the
    next wave, NOT flagged as overflow); the convergence-verification
    wide cycle passes 2.

    ``prescreen``: the nomination-time degeneracy prescreen below; a
    Python bool compiles it in or out, a traced scalar bool switches it
    at run time inside one compiled program (the grouped cycle blocks).

    ``et``/``lens``: a caller-precomputed edge table + metric lengths of
    THIS mesh (adapt_cycle_impl builds one table serving both split and
    collapse — the tables are a measured hot spot of every wave).
    """
    capT, capP = mesh.capT, mesh.capP
    if et is None:
        et = unique_edges(mesh)
    if lens is None:
        lens = edge_lengths(mesh, et, met)

    # --- candidate edges -------------------------------------------------
    va = jnp.clip(et.ev[:, 0], 0, capP - 1)
    vb = jnp.clip(et.ev[:, 1], 0, capP - 1)
    frozen_edge = (et.etag & (MG_REQ | MG_PARBDY)) != 0
    # what the candidacy reads of an endpoint rides in ONE row a vertex
    # (ops/rowpack: a row gather costs a third of one scalar's)
    cols = {}
    if fem_only or hausd is not None:
        cols["tag"] = mesh.vtag
    if hausd is not None:
        from .analysis import boundary_vertex_normals, carries_normal, \
            ridge_vertex_tangents
        from ..core.constants import MG_CRN, MG_NOM
        if vn is None:      # else the caller's, of THIS mesh
            vn = boundary_vertex_normals(mesh)
        tan = vtan if vtan is not None \
            else ridge_vertex_tangents(mesh, et=et)
        # an endpoint has ONE normal unless it is a feature point or
        # frozen; a frozen seam vertex has one again where the mesh
        # carries it (its own fan is only the seam's near side)
        one_n = ((mesh.vtag & (MG_GEO | MG_CRN | MG_NOM | MG_REF)) == 0) & \
            (((mesh.vtag & (MG_REQ | MG_PARBDY)) == 0) |
             carries_normal(mesh))
        cols.update(one_n=one_n, p=mesh.vert, n=vn, tan=tan)
    if cols:
        ends = pack(**cols)
        end_a, end_b = ends.take(va), ends.take(vb)
    if fem_only:
        both_bdy = ((end_a["tag"] & MG_BDY) != 0) & \
            ((end_b["tag"] & MG_BDY) != 0)
        cand = et.emask & ((et.etag & MG_BDY) == 0) & both_bdy & \
            ~frozen_edge
    else:
        cand = et.emask & (lens > lmax) & ~frozen_edge
    lift_corr = None
    if hausd is not None:
        regular = ((et.etag & MG_BDY) != 0) & \
            ((et.etag & (MG_GEO | MG_REQ | MG_PARBDY | MG_REF)) == 0) & \
            end_a["one_n"] & end_b["one_n"]
        d = end_b["p"] - end_a["p"]
        na, nb = end_a["n"], end_b["n"]
        t_a = d - na * jnp.sum(na * d, -1, keepdims=True)
        t_b = d - nb * jnp.sum(nb * d, -1, keepdims=True)
        corr = 0.125 * (t_a - t_b)                     # Bezier mid offset
        # refinement pressure comes from the METRIC (hausd_metric_bound
        # folds sqrt(8*hausd/kappa) into boundary sizes, the Mmg defsiz
        # route); here hausd only drives point PLACEMENT
        lift_corr = jnp.where(regular[:, None], corr, 0.0)
        # curved FEATURE LINES (ridge/ref edges between two plain
        # ridge/ref points): lift the midpoint along the tangent circle
        # of the feature curve — the Hermite analogue of the surface
        # lift with the edge vector projected on each endpoint's LINE
        # tangent (the reference keeps per-point tangents in the xPoint
        # and maintains them across ranks, analys_pmmg.c:199-1171).
        # Without this, curved ridges (torus equator class) stay
        # piecewise-linear no matter how fine the metric.
        hard = MG_CRN | MG_REQ | MG_PARBDY | MG_NOM
        on_line = ((et.etag & (MG_GEO | MG_REF)) != 0) & \
            ((et.etag & (MG_REQ | MG_PARBDY)) == 0) & \
            ((end_a["tag"] & hard) == 0) & \
            ((end_b["tag"] & hard) == 0)
        ta_l = end_a["tan"] * jnp.sum(end_a["tan"] * d, -1, keepdims=True)
        tb_l = end_b["tan"] * jnp.sum(end_b["tan"] * d, -1, keepdims=True)
        corr_l = 0.125 * (ta_l - tb_l)
        lift_corr = jnp.where(on_line[:, None], corr_l, lift_corr)
    # Everything below (nomination, degeneracy veto, winner
    # selection, apply) is lax.cond-skipped when NO candidate edge
    # exists — at convergence the wave then costs only the table +
    # candidacy masks.
    def _idle(_):
        return SplitResult(mesh, met, jnp.zeros((), jnp.int32),
                           jnp.zeros((), bool),
                           jnp.zeros(capT, bool), jnp.zeros((), bool),
                           jnp.zeros((), jnp.int32))

    def _act(_):
        from .quality import quality_from_points
        from ..core.constants import QUAL_FLOOR, SPLIT_CHILD_FLOOR
        from .edges import topk_prep, wave_budget
        # what a child must keep: the floor of degeneracy, and in a
        # sizing split under a scalar size map SPLIT_CHILD_FLOOR where
        # the tet holds a frozen seam edge (a fem split is owed whatever
        # its children are; the floor is Euclidean, so a tensor metric,
        # in which a well-shaped tet may read far under it, keeps
        # QUAL_FLOOR everywhere)
        child_floor = QUAL_FLOOR
        seam_floor = not fem_only and met.ndim == 1
        if seam_floor:
            on_seam = jnp.any((mesh.etag & MG_PARBDY) != 0, axis=1)
            child_floor = jnp.where(on_seam, SPLIT_CHILD_FLOOR, QUAL_FLOOR)
        capE = et.ev.shape[0]
        ar0 = jnp.arange(capT)
        s, t = claim_channels(lens, cand)                 # sort-free priority

        # --- nomination: each tet picks its (s,t)-max candidate edge ---------
        # both channels ride ONE [capT,6,2] gather (t bitcast to f32 lanes)
        st = jnp.stack([s, jax.lax.bitcast_convert_type(t, jnp.float32)],
                       axis=1)                            # [capE,2]
        st_te = st[et.edge_id]                            # [capT,6,2]
        tes = jnp.where(mesh.tmask[:, None], st_te[..., 0], NEG_INF)
        t_te = jax.lax.bitcast_convert_type(st_te[..., 1], jnp.int32)
        best_s = jnp.max(tes, axis=1)                     # [capT]
        at_best = (tes == best_s[:, None]) & jnp.isfinite(best_s)[:, None]
        tet_t = jnp.where(at_best, t_te, PRI_MIN)
        best_t = jnp.max(tet_t, axis=1)
        # exactly one slot per tet (t is unique): the whole-shell win test
        # below stays exact under simultaneous application
        nominate = at_best & (tet_t == best_t[:, None])
        # nomination-time degeneracy prescreen: split children inherit
        # >= half the parent quality (the midpoint halves the volume
        # exactly and no child edge exceeds a parent edge), so only
        # near-degenerate parents can produce sub-floor children.  Veto
        # their nominations HERE so such shells never pin top-K budget
        # slots wave after wave (starvation); the exact [KH] veto below
        # stays as the precise guard (incl. hausd-lifted midpoints,
        # where the half-quality bound is only approximate — the bound
        # is NOT exact for the quality measure, so near-floor parents
        # can be over-vetoed).  The 2x margin (was 4x, ADVICE r3: the
        # wide margin permanently blocked near-floor shells whose
        # children pass the exact veto, stalling refinement in
        # low-quality regions) keeps the starvation guard while halving
        # the over-veto band; the wide convergence-verification cycle
        # AND the drivers' polish cycles pass prescreen=False so any
        # still-blocked shell gets an exact re-evaluation.
        static = isinstance(prescreen, (bool, np.bool_))
        if not static or prescreen:
            q_par = quality_from_points(mesh.vert[mesh.tet])
            keep = q_par > 2.0 * child_floor
            if not static:          # traced switch: one compiled program
                keep = keep | ~prescreen
            nominate = nominate & keep[:, None]
        has_nom = jnp.any(nominate, axis=1)
        loc_n = jnp.argmax(nominate, axis=1)              # [capT]
        e_n = jnp.clip(et.edge_id[ar0, loc_n], 0, capE - 1)

        # --- an edge wins iff nominated by its whole shell -------------------
        # each tet nominates at most ONE edge, so the count scatters at
        # [capT] width (not [6*capT] — scatter cost is linear in index
        # count, scripts/tpu_microbench.py)
        nom_count = jnp.zeros(capE, jnp.int32).at[
            jnp.where(has_nom, e_n, capE)].add(1, mode="drop")
        win0 = cand & (nom_count == et.nshell) & (et.nshell > 0)

        # --- budget: top-K winners by priority (longest edges first) ---------
        # replaces a full-width argsort + 6 full-width cumsums with ONE
        # top_k and [KW]-width prefix sums (the budget/offset stage was
        # ~30 ms of the wave; a block by phase: PERF.md section 5)
        KW = min(wave_budget(capT, budget_div), capE)
        KH = min(2 * wave_budget(capT, budget_div), capT)
        # fused scoring prep (ops/edges.topk_prep wants smallest-first,
        # so pass -lens: -(-lens) is a sign-bit round-trip, bit-exact)
        neg, nwin = topk_prep(win0, -lens)
        vals, wc = jax.lax.top_k(neg, KW)
        wv = vals > NEG_INF                               # real winners
        wcc = jnp.clip(wc, 0, capE - 1)
        # the KH shell-tet budget must bound the winner set BEFORE the
        # row compaction below — rows past the static compaction size
        # would be silently dropped, splitting only part of a shell
        sh0 = jnp.where(wv, et.nshell[wcc], 0)
        toff0 = jnp.cumsum(sh0) - sh0
        shell_fit = (toff0 + sh0) <= KH
        # budget deferral (top-K or shell-budget cut of VIABLE winners —
        # gate/capacity drops are flagged elsewhere)
        defer = (nwin > KW) | jnp.any(wv & ~shell_fit)
        wv = wv & shell_fit

        # --- degeneracy veto (MMG5_split1b cavity-quality check) -------------
        # evaluated on the [KH]-compacted shells of the budget winners
        # instead of all capT tets: a shell tet whose child would be
        # degenerate vetoes the whole edge (the wave simply skips it; the
        # old nomination-time veto had the same final effect)
        keep0 = jnp.zeros(capE, bool).at[jnp.where(wv, wc, capE)].set(
            True, mode="drop", unique_indices=True)
        has0 = has_nom & keep0[e_n]
        hidx = jnp.nonzero(has0, size=KH, fill_value=capT)[0]
        hv0 = hidx < capT
        hc = jnp.clip(hidx, 0, capT - 1)
        arK = jnp.arange(KH)
        loc0 = loc_n[hc]
        e0 = jnp.clip(e_n[hc], 0, capE - 1)
        il = _IARE_J[loc0, 0]                             # [KH]
        jl = _IARE_J[loc0, 1]
        rows0 = mesh.tet[hc]                              # [KH,4]
        mid_row = 0.5 * (mesh.vert[va[e0]] + mesh.vert[vb[e0]])
        if lift_corr is not None:
            mid_row = mid_row + lift_corr[e0]
        pts0 = mesh.vert[rows0]                           # [KH,4,3]
        q1 = quality_from_points(pts0.at[arK, jl].set(mid_row))
        q2 = quality_from_points(pts0.at[arK, il].set(mid_row))
        floor0 = child_floor[hc] if seam_floor else child_floor
        rowbad = hv0 & ~((q1 > floor0) & (q2 > floor0))
        veto_e = jnp.zeros(capE + 1, bool).at[
            jnp.where(rowbad, e0, capE)].max(rowbad, mode="drop")[:capE]

        # --- final winner set + offsets, all at [KW] width -------------------
        # allocation pools: reuse rows freed by earlier collapses (not a
        # watermark cursor — see edges.free_rows)
        from .edges import free_rows
        okv = wv & ~veto_e[wcc]
        win_i = okv.astype(jnp.int32)
        new_off = jnp.cumsum(win_i) - win_i
        frow_p, nfree_p = free_rows(mesh.vmask, KW)
        fits_p = new_off < jnp.minimum(nfree_p, KW)
        sh = jnp.where(okv & fits_p, et.nshell[wcc], 0)
        toff = jnp.cumsum(sh) - sh
        frow_t, nfree_t = free_rows(mesh.tmask, KH)
        fits_cap = fits_p & ((toff + sh) <= jnp.minimum(nfree_t, KH))
        ok = okv & fits_cap
        # overflow = CAPACITY-dropped winners only (triggers a host
        # regrow); budget- or veto-dropped winners just defer
        overflow = jnp.any(okv & ~fits_cap)
        nwin = jnp.sum(ok.astype(jnp.int32))

        # midpoint coordinates / refs / tags on the [KW] winner rows
        va_w, vb_w = va[wcc], vb[wcc]
        pa, pb = mesh.vert[va_w], mesh.vert[vb_w]
        mid = 0.5 * (pa + pb)
        if lift_corr is not None:
            mid = mid + lift_corr[wcc]            # onto the Bezier surface
        mid_id_w = frow_p[jnp.clip(new_off, 0, KW - 1)]
        tgt_w = jnp.where(ok, mid_id_w, capP)
        vert = mesh.vert.at[tgt_w].set(mid, mode="drop", unique_indices=True)
        vmask = mesh.vmask.at[tgt_w].set(True, mode="drop",
                                         unique_indices=True)
        # the new point inherits the edge's tags (a point on a ridge edge is a
        # ridge point, on a boundary edge a boundary point, ...)
        vtag = mesh.vtag.at[tgt_w].set(et.etag[wcc], mode="drop",
                                       unique_indices=True)
        vref = mesh.vref.at[tgt_w].set(
            jnp.minimum(mesh.vref[va_w], mesh.vref[vb_w]), mode="drop",
            unique_indices=True)
        met_new = met.at[tgt_w].set(_interp_met_mid(met, va_w, vb_w),
                                    mode="drop", unique_indices=True)

        # --- allocation tables: midpoint vid + free-pool base per edge -------
        # ONE packed [KW] scatter; -1 marks non-winning edges.  Column 1
        # is the edge's base OFFSET into the frow_t free pool (its shell
        # tets take consecutive pool entries, not consecutive slots)
        alloc = jnp.full((capE, 2), -1, jnp.int32).at[
            jnp.where(ok, wc, capE)].set(
            jnp.stack([mid_id_w, toff.astype(jnp.int32)], axis=1),
            mode="drop", unique_indices=True)

        # --- split shell tets on the same [KH] compaction --------------------
        # shell tets of a winning edge are exactly the tets that nominated
        # it (whole-shell rule), so the pre-veto compaction rows are reused
        # with an updated validity mask — no second nonzero pass
        al_row = alloc[e0]                                # [KH,2]
        hv = hv0 & (al_row[:, 0] >= 0)
        mh = jnp.clip(al_row[:, 0], 0, capP - 1)
        # rank of this tet within its shell -> new tet slot from the
        # free pool (the shell rank precomputed by unique_edges:
        # sorted-segment rank)
        new_tid_r = frow_t[jnp.clip(al_row[:, 1] + et.shell_rank[hc, loc0],
                                    0, KH - 1)]
        tgt1 = jnp.where(hv, hc, capT)
        tgt2 = jnp.where(hv, jnp.clip(new_tid_r, 0, capT - 1), capT)
        # tet1 (in place): vertex j -> m ; tet2 (new slot): vertex i -> m
        tet1_rows = rows0.at[arK, jl].set(mh, unique_indices=True)
        tet2_rows = rows0.at[arK, il].set(mh, unique_indices=True)
        tet_out = mesh.tet.at[tgt1].set(tet1_rows, mode="drop",
                                        unique_indices=True)
        tet_out = tet_out.at[tgt2].set(tet2_rows, mode="drop",
                                       unique_indices=True)
        tmask = mesh.tmask.at[tgt2].set(True, mode="drop",
                                        unique_indices=True)
        tref = mesh.tref.at[tgt2].set(mesh.tref[hc], mode="drop",
                                      unique_indices=True)

        # --- tag inheritance (on the compacted rows) --------------------------
        # tet1 keeps its ftag/etag except: the cut face (opposite i) becomes
        # interior (tag 0); the half edges adjacent to the cut inherit; new
        # edges (m,c) inside an old face f inherit that face's boundary bit.
        ftag1r, fref1r, etag1r, ftag2r, fref2r, etag2r = _split_tags_rows(
            mesh, hc, il, jl)
        ftag = mesh.ftag.at[tgt1].set(ftag1r, mode="drop",
                                      unique_indices=True)
        ftag = ftag.at[tgt2].set(ftag2r, mode="drop", unique_indices=True)
        frf = mesh.fref.at[tgt1].set(fref1r, mode="drop",
                                     unique_indices=True)
        frf = frf.at[tgt2].set(fref2r, mode="drop", unique_indices=True)
        etag_out = mesh.etag.at[tgt1].set(etag1r, mode="drop",
                                          unique_indices=True)
        etag_out = etag_out.at[tgt2].set(etag2r, mode="drop",
                                         unique_indices=True)

        # watermarks stay monotone upper bounds over used rows (pool
        # rows may lie below the old watermark — reuse tightens nothing)
        npoin = jnp.maximum(mesh.npoin,
                            jnp.max(jnp.where(ok, mid_id_w + 1, 0)))
        nelem = jnp.maximum(
            mesh.nelem, jnp.max(jnp.where(hv, new_tid_r + 1, 0)))
        out = dataclasses.replace(
            mesh, vert=vert, vmask=vmask, vtag=vtag, vref=vref,
            tet=tet_out, tmask=tmask, tref=tref,
            ftag=ftag, fref=frf, etag=etag_out,
            npoin=npoin.astype(jnp.int32), nelem=nelem.astype(jnp.int32))
        # tets rewritten in place (tgt1) or created (tgt2) this wave — the
        # staleness footprint for a collapse sharing our edge table
        modified = jnp.zeros(capT, bool).at[tgt1].set(
            True, mode="drop", unique_indices=True).at[tgt2].set(
            True, mode="drop", unique_indices=True)
        nbdy = jnp.sum(ok & ((et.etag[wcc] & MG_BDY) != 0),
                       dtype=jnp.int32)
        return SplitResult(out, met_new, nwin, overflow, modified, defer,
                           nbdy)

    return jax.lax.cond(jnp.any(cand), _act, _idle, None)


def _split_tags_rows(mesh: Mesh, hc, il, jl):
    """Tag inheritance for the two halves of each split tet, computed on
    the COMPACTED affected rows [KH] (hc = affected tet ids).

    For split edge at local (i,j) with midpoint m:
      tet1 = tet with v_j := m, tet2 = tet with v_i := m.
      - face opposite the replaced vertex is the *outer* original face
        (unchanged): inherits.
      - faces k not in {i,j} are cut in half: inherit original face k tags.
      - the cut face (opposite the kept edge endpoint) is interior: tag 0.
      - edges: the split edge's halves inherit its tag; new edges m-c lie
        inside original faces: they get MG_BDY/MG_REF iff that face has it;
        other edges inherit.
    """
    KH = hc.shape[0]
    arK = jnp.arange(KH)
    ftag0 = mesh.ftag[hc]                                  # [KH,4]
    fref0 = mesh.fref[hc]
    etag0 = mesh.etag[hc]                                  # [KH,6]

    def one_half(repl):  # repl [KH] = local vertex replaced by m
        kept = jnp.where(repl == il, jl, il)
        # cut face = face opposite `kept` -> interior
        ftag = ftag0.at[arK, kept].set(0, unique_indices=True)
        fref = fref0.at[arK, kept].set(0, unique_indices=True)
        # edges: for each local edge, decide inheritance.  New edges
        # incident to `repl` (other endpoint c not in {i,j}) lie inside
        # the original face containing {i, j, c} = the face opposite the
        # remaining vertex; they inherit that face's MG_BDY/MG_REF.
        out = etag0
        for el in range(6):
            a, b = int(IARE[el][0]), int(IARE[el][1])
            av = jnp.int32(a)
            bv = jnp.int32(b)
            touches_repl = (av == repl) | (bv == repl)
            other = jnp.where(av == repl, bv, av)
            is_split_edge = ((av == il) & (bv == jl)) | \
                            ((av == jl) & (bv == il))
            # remaining vertex = the one not in {i, j, other}; 0+1+2+3=6
            rem = (jnp.int32(6) - (il + jl + other)).astype(jnp.int32)
            in_old_face = touches_repl & ~is_split_edge & \
                (other != il) & (other != jl)
            face_t = ftag0[arK, jnp.clip(rem, 0, 3)]
            new_t = (face_t & (MG_BDY | MG_REF)).astype(jnp.uint32)
            val = jnp.where(in_old_face, new_t, out[:, el])
            out = out.at[:, el].set(val)
        return ftag, fref, out

    ftag1, fref1, etag1 = one_half(jl)
    ftag2, fref2, etag2 = one_half(il)
    return ftag1, fref1, etag1, ftag2, fref2, etag2
