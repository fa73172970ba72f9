"""Batched vertex smoothing — data-parallel replacement for Mmg's movtet.

Reference behavior: ``MMG5_movtet`` relocates free vertices to improve local
quality (volume barycenter moves for interior points — ``MMG5_movintpt``;
tangential moves for regular surface points — ``MMG5_movbdyregpt``), never
degrading the worst quality of the ball; required / corner / ridge /
parallel-interface points are frozen (the ParMmg contract,
tag_pmmg.c:39-124).

Wave scheme: every movable vertex proposes a new position (ball-centroid
for interior points; tangent-plane-projected surface-centroid for regular
boundary points, on locally-flat patches as it is and on curved ones,
given ``hausd``, put back onto the surface the fan describes); validity (ball min-quality must
not decrease) is checked tet-centrically; a hash-rotated independent set
(vertex claims all its ball tets) moves per wave so the precheck remains
exact under simultaneous moves.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh
from ..core.constants import (
    IDIR, MG_BDY, MG_CRN, MG_GEO, MG_NOM, MG_REF, MG_REQ, MG_PARBDY,
    EPSD, QUAL_FLOOR)
from .quality import quality_from_points
from .edges import PRI_MIN
from . import rowpack

# a regular surface point slides in its tangent plane when its incident
# boundary faces are mutually near-parallel — the move is then
# surface-exact.  Gate: |sum of unit normals| / count >= FLAT_RATIO,
# i.e. a single outlier face in a 12-face ball may tilt ~4 deg (the old
# per-face min-dot gate allowed 2.6 deg but cost a second full-width
# gather+scatter pass per wave)
FLAT_RATIO = 0.9998
# on a curved patch, with a surface tolerance ``hausd`` given, it slides
# too and is then put back ON the surface (Mmg's movbdyregpt reprojects
# onto the Bezier patch): the fan's own vertices give the surface's
# second fundamental form II at the point and the normal it corrects
# (analysis.boundary_second_form, five unknowns fitted over the spokes),
# and a step s t in THAT tangent plane leaves the surface by
# II(t, t) s^2 / 2 along the normal, the normal curvature of the step's
# OWN direction: on a torus that is 1 / r round the tube and
# cos(theta) / rho along the ring, of the other sign on the inner half,
# and one number for both leaves every slide off the surface; on a
# sphere II is isotropic and the step is exact to O(s^4).  Gates: the
# fan's faces lie in a cone round the vertex normal (ratio >=
# SMOOTH_RATIO, 18 deg: a crease the analysis left untagged does not
# slide), the fan has as many spokes as the fit has unknowns
# (SLIDE_SPOKES: the priors decide what four spokes leave open, and
# such a fan's normal stood up to 0.07 rad off a torus's), and the step
# leaves the old surface by no more than hausd.  What no gate bounds: a
# slide puts the vertex on the surface its NEIGHBOURS describe, so the
# fits' errors add up over a job's moves (a surface vertex moves twice
# on average); on a torus of tube radius 0.4 the farthest vertex of a
# job stands 5e-4 to 2.4e-3 off it, a lifted midpoint alone 5e-4
SMOOTH_RATIO = 0.95
SLIDE_SPOKES = 5


class SmoothResult(NamedTuple):
    mesh: Mesh
    nmoved: jax.Array
    nbdy: jax.Array = None   # of ``nmoved``, the surface vertices


def smooth_wave(mesh: Mesh, met: jax.Array, wave: int = 0,
                relax: float = 1.0,
                opt_q: float | None = None,
                hausd: float | None = None, lists=None) -> SmoothResult:
    """One smoothing wave; see module docstring.

    ``hausd``: the surface tolerance (Mmg -hausd).  With it a regular
    surface vertex on a CURVED patch slides too and is reprojected onto
    the surface its fan describes, by the curvature of the direction it
    moved in (``SMOOTH_RATIO``, ``SLIDE_SPOKES`` above); without it
    only flat patches slide, where no reprojection is needed.

    ``opt_q``: optimal-position mode for sliver balls — interior
    vertices whose ball min quality is below ``opt_q`` propose a move
    along the HEIGHT direction of their worst incident tet (direct
    ascent on that tet's quality) instead of the ball centroid; the
    centroid is blind to the worst member and plateaus exactly where
    the min needs lifting (Mmg's bad-element relocation in MMG3D_opttyp
    serves this role).  The relaxation cascade and the exact ball
    min-quality gate are unchanged.

    ``lists``: an ``ops/surflist.Tally`` (default: one that observes
    where the program is placed); where it is on, the surface sums and
    the face geometry under them run over the listed (face, corner)
    records of the boundary faces alone, and the second form's moments
    over the tets that hold one; the lists' updates are counted into it.

    Fixed-point invariant (the quiet-group scheduler's proof rests on
    it, parallel/sched.py): ``nmoved == 0`` iff NO vertex has an
    accepted improving move — the globally best improving vertex can
    never lose a claim, so an empty accepted set means the improving
    set itself is empty, and that emptiness is invariant under the
    ``wave`` rotation (proposals are wave-independent; ``wave`` only
    rotates claim tie-breaks among winners).  A zero-move wave is
    therefore an exact identity on the mesh.
    """
    capT, capP = mesh.capT, mesh.capP
    movable_int = mesh.vmask & ((mesh.vtag &
                                 (MG_BDY | MG_REQ | MG_CRN | MG_PARBDY))
                                == 0)
    reg_bdy = mesh.vmask & ((mesh.vtag & MG_BDY) != 0) & \
        ((mesh.vtag & (MG_REQ | MG_CRN | MG_PARBDY | MG_GEO | MG_NOM |
                       MG_REF)) == 0)

    tv = mesh.tet
    vpos = mesh.vert[tv]                                   # [T,4,3]
    centroid = jnp.mean(vpos, axis=1)                      # [T,3]
    # proposal: mean of ball-tet centroids (volume-barycenter flavor of
    # MMG5_movintpt).  All 4 corners accumulate in ONE concatenated wide
    # scatter — per-op overhead dominates scatter cost on this device
    # (scripts/tpu_microbench.py: cost is flat in payload width).
    idx4 = jnp.concatenate(
        [jnp.where(mesh.tmask, tv[:, k], capP) for k in range(4)])
    pay = jnp.concatenate([jnp.concatenate(
        [centroid, jnp.ones((centroid.shape[0], 1), mesh.vert.dtype)],
        axis=1)] * 4)                                      # [4T, 4]
    acc4 = jnp.zeros((capP + 1, 4), mesh.vert.dtype).at[idx4].add(
        pay, mode="drop")
    prop = acc4[:capP, :3] / jnp.maximum(acc4[:capP, 3:], 1.0)

    # --- surface proposals (movbdyregpt): tangential move on flat patch --
    from . import surflist
    from .analysis import (FaceCorner, SecondForm, boundary_second_form,
                           corner_weights, face_corner_list)
    lists = surflist.Tally() if lists is None else lists
    idir = jnp.asarray(IDIR)
    isb = ((mesh.ftag & MG_BDY) != 0) & mesh.tmask[:, None]   # [T,4]

    # all 12 (face, corner) contributions in ONE wide scatter:
    # payload = (corner-weighted normal[3], area*centroid[3], area[1],
    #            unit normal[3], count[1]) — the unit-normal sum
    # feeds the gates below with no second full-width pass.  The corner
    # weights are those of analysis.boundary_vertex_normals
    if lists.on:
        live = face_corner_list(isb, lists)

        def updates(p, ok):
            c = FaceCorner(mesh, p)
            fn = jnp.cross(c.ea, c.eb)
            farea = 0.5 * jnp.sqrt(jnp.sum(fn * fn, -1))[:, None]
            fn_unit = fn / (jnp.linalg.norm(fn, axis=-1, keepdims=True)
                            + EPSD)
            pay = jnp.concatenate(
                [fn * c.weight[:, None], farea * jnp.mean(c.fp, axis=1),
                 farea, fn_unit, jnp.ones_like(farea)], axis=-1)
            return jnp.where(ok, c.vid, capP), pay
        sacc = surflist.staged_scatter(
            jnp.zeros((capP + 1, 11), mesh.vert.dtype), live,
            updates)[:capP]
    else:
        fv = tv[:, idir]                                   # [T,4,3] vids
        fp = mesh.vert[fv]                                 # [T,4,3,3]
        ea, eb = fp[:, :, 1] - fp[:, :, 0], fp[:, :, 2] - fp[:, :, 0]
        fn = jnp.cross(ea, eb)                             # [T,4,3] outward
        fc = jnp.mean(fp, axis=2)                          # [T,4,3]
        farea = 0.5 * jnp.sqrt(jnp.sum(fn * fn, -1))       # [T,4]
        idx12 = jnp.concatenate(
            [jnp.where(isb[:, f], fv[:, f, k], capP)
             for f in range(4) for k in range(3)])
        w4 = jnp.where(isb, farea, 0.0)                    # [T,4]
        fn_unit = fn / (jnp.linalg.norm(fn, axis=-1, keepdims=True) + EPSD)
        pay_f = jnp.concatenate(
            [w4[..., None] * fc, w4[..., None], fn_unit,
             jnp.ones_like(w4)[..., None]], axis=-1)       # [T,4,8]
        wgt = corner_weights(ea, eb)                       # [T,4,3]
        pay12 = jnp.concatenate(
            [jnp.concatenate(
                [fn[:, f] * wgt[:, f, k, None], pay_f[:, f]], axis=-1)
             for f in range(4) for k in range(3)])         # [12T,11]
        sacc = jnp.zeros((capP + 1, 11), mesh.vert.dtype).at[idx12].add(
            pay12, mode="drop")[:capP]
    nacc, cacc, aacc = sacc[:, :3], sacc[:, 3:6], sacc[:, 6]
    uacc, ucnt = sacc[:, 7:10], sacc[:, 10]
    navg = nacc / (jnp.linalg.norm(nacc, axis=-1, keepdims=True) + EPSD)
    # locally-flat gate: |sum of unit normals| close to the face count
    # means every incident boundary face is near the common plane
    ratio = jnp.linalg.norm(uacc, axis=-1) / jnp.maximum(ucnt, 1.0)
    flat = (ratio >= FLAT_RATIO) & (aacc > 0)
    cbar = cacc / jnp.maximum(aacc[:, None], EPSD)
    dvec = cbar - mesh.vert
    if hausd is not None:
        # a curved patch: the surface's second form over the fan, and
        # the normal it corrects (the weighted sum of facet normals is
        # exact on a sphere only, and a tangent plane that is tilted by
        # delta leaves the surface by delta s, in the first order of
        # the step).  A mesh with no curved patch to slide on (a box)
        # skips the fit, its gather and its scatter
        curved = reg_bdy & ~flat & (ratio >= SMOOTH_RATIO) & (aacc > 0)
        zero3 = jnp.zeros((capP, 3), mesh.vert.dtype)

        def no_fit():
            return SecondForm(zero3, zero3, zero3, navg, zero3[:, 0])
        if lists.on:
            sf, nfit = jax.lax.cond(
                jnp.any(curved),
                lambda: surflist.counted(boundary_second_form, mesh, navg,
                                         isb),
                lambda: (no_fit(), jnp.zeros((), jnp.int32)))
            lists.note(nfit)
        else:
            sf = jax.lax.cond(
                jnp.any(curved),
                lambda: boundary_second_form(mesh, navg, isb, lists=lists),
                no_fit)
        navg = jnp.where(flat[:, None], navg, sf.normal)
    dvec = dvec - jnp.sum(dvec * navg, -1, keepdims=True) * navg
    if hausd is None:
        bdy_ok = reg_bdy & flat
        drop = jnp.zeros(capP, mesh.vert.dtype)
    else:
        # the surface under the tangent plane along the step (at step 1)
        drop = 0.5 * sf.along(dvec)
        bdy_ok = reg_bdy & (flat | (
            curved & (sf.spokes >= SLIDE_SPOKES)
            & (jnp.abs(drop) <= hausd)))
    drop = jnp.where(bdy_ok & ~flat, drop, 0.0)[:, None] * navg
    prop = jnp.where(bdy_ok[:, None], mesh.vert + dvec, prop)
    movable = movable_int | bdy_ok

    # --- validity: per-ball min quality must not decrease ----------------
    # Try a cascade of relaxation factors (Mmg's movtet retries with damped
    # steps); each vertex takes the largest step whose ball min-quality
    # strictly improves.
    # iso: Euclidean quality (MMG5_caltet_iso — local scaling cancels);
    # aniso: per-corner packed tensors.  Skipping the [T,4,6] gather and
    # the tensor math in the 12 quality evaluations below is a large TPU
    # win per wave.
    mq = None if met.ndim == 1 else met[tv]                # [T,4,6] | None
    q_old = quality_from_points(vpos, mq)                  # [T]
    minq_old = jnp.full(capP + 1, jnp.inf, mesh.vert.dtype).at[idx4].min(
        jnp.tile(jnp.where(mesh.tmask, q_old, jnp.inf), 4), mode="drop")
    minq_old = minq_old[:capP]

    if opt_q is not None:
        # worst-incident-tet height ascent: for each (tet, corner) whose
        # tet attains the vertex's ball minimum, the perpendicular from
        # the opposite face plane to the corner is the quality gradient
        # direction (moving +d doubles that tet's height); ties average.
        sworst = jnp.where(mesh.tmask, -q_old, -jnp.inf)
        vworst = jnp.full(capP + 1, -jnp.inf, mesh.vert.dtype).at[
            idx4].max(jnp.tile(sworst, 4), mode="drop")[:capP]
        dacc = jnp.zeros((capP + 1, 4), mesh.vert.dtype)
        # one row a corner (ops/rowpack), not four 1-D gathers
        vworst_c = rowpack.take(vworst, tv)               # [T,4]
        for k in range(4):
            fidx = idir[k]                                 # face opp k
            p0 = vpos[:, fidx[0]]
            nrm = jnp.cross(vpos[:, fidx[1]] - p0, vpos[:, fidx[2]] - p0)
            n2 = jnp.maximum(jnp.sum(nrm * nrm, -1, keepdims=True), EPSD)
            d = nrm * (jnp.sum((vpos[:, k] - p0) * nrm, -1,
                               keepdims=True) / n2)        # [T,3]
            is_w = mesh.tmask & (sworst >= vworst_c[:, k])
            pay = jnp.concatenate(
                [jnp.where(is_w[:, None], d, 0.0),
                 is_w[:, None].astype(mesh.vert.dtype)], axis=1)
            dacc = dacc.at[jnp.where(is_w, tv[:, k], capP)].add(
                pay, mode="drop")
        cnt = jnp.maximum(dacc[:capP, 3:], 1.0)
        prop_opt = mesh.vert + dacc[:capP, :3] / cnt
        use_opt = movable_int & (minq_old < opt_q) & \
            (dacc[:capP, 3] > 0)
        prop = jnp.where(use_opt[:, None], prop_opt, prop)

    # the 4 per-corner displacement variants are evaluated as ONE stacked
    # quality call per relaxation step (4x batch ~ free, 4 calls are not)
    mq4 = None if mq is None else jnp.tile(mq, (4, 1, 1))
    newpos = mesh.vert
    best_gain = jnp.zeros(capP, mesh.vert.dtype)
    # NOTE a two-step cascade (dropping 0.25) was tried for the ~20 ms
    # saving and reverted: the small step is load-bearing for final edge-
    # length conformity (test_adapt_target_lengths regressed without it)
    for step in (relax, 0.5 * relax, 0.25 * relax):
        # a curved patch's slide goes back onto the surface: the normal
        # part of a step grows with its square
        cand_pos = mesh.vert + step * (prop - mesh.vert) - \
            (step * step) * drop
        cand_pos = jnp.where(movable[:, None], cand_pos, mesh.vert)
        newp = cand_pos[tv]                                # [T,4,3]
        variants = jnp.concatenate(
            [vpos.at[:, k].set(newp[:, k]) for k in range(4)])  # [4T,4,3]
        qv = quality_from_points(variants, mq4)            # [4T]
        minq_new = jnp.full(capP + 1, jnp.inf, mesh.vert.dtype).at[
            idx4].min(jnp.where(jnp.tile(mesh.tmask, 4), qv, jnp.inf),
                      mode="drop")
        gain = minq_new[:capP] - minq_old
        ok = (minq_new[:capP] > jnp.maximum(minq_old, QUAL_FLOOR)) & movable
        take = ok & (gain > best_gain)
        newpos = jnp.where(take[:, None], cand_pos, newpos)
        best_gain = jnp.where(take, gain, best_gain)
    # minimum-gain gate (Mmg's movers demand a real improvement too):
    # balls already above the sliver threshold only move for a >=2%
    # relative lift of their min quality — without this, centroid
    # micro-moves churn forever at steady state (each move re-creates
    # short edges for the collapse pass), so a converged mesh never
    # reaches the cheap idle cycles; bad balls keep the any-gain rule
    gain_tol = jnp.where(minq_old < 0.2, 0.0, 0.02 * minq_old)
    improves = best_gain > gain_tol

    # --- independent set: vertex claims its ball tets --------------------
    # wave-rotated hash: a full-avalanche BIJECTIVE mix (odd multiplies +
    # xor-shifts, invertible mod 2^32), so per-wave priorities are unique
    # by construction and usable directly as the claim order — no sort
    wv = jnp.asarray(wave, jnp.uint32)
    h = jnp.arange(capP, dtype=jnp.uint32) * jnp.uint32(2654435761)
    h = h + wv * jnp.uint32(2246822519)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(2654435761)
    h = h ^ (h >> 13)
    vpri = jnp.where(improves, h.astype(jnp.int32), PRI_MIN)
    # a corner's priority and whether it improves ride in ONE row
    # (ops/rowpack: a row gather costs a third of one scalar's)
    corner = rowpack.pack(pri=vpri, improves=improves).take(tv)
    vpri_c = corner["pri"]                                 # [T,4]
    tclaim = jnp.max(jnp.where(mesh.tmask[:, None], vpri_c, PRI_MIN),
                     axis=1)
    mism4 = jnp.concatenate(
        [corner["improves"][:, k] & (tclaim != vpri_c[:, k])
         for k in range(4)])
    lost = jnp.zeros(capP + 1, bool).at[idx4].max(mism4, mode="drop")
    win = improves & ~lost[:capP]

    vert = jnp.where(win[:, None], newpos, mesh.vert)
    return SmoothResult(dataclasses.replace(mesh, vert=vert),
                        jnp.sum(win.astype(jnp.int32)),
                        jnp.sum(win & bdy_ok, dtype=jnp.int32))
