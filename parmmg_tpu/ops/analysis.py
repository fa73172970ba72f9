"""Surface analysis: boundary extraction, ridges, corners, normals.

TPU-native equivalent of the sequential analysis the reference delegates to
Mmg (``MMG3D_analys``: ``setadj``/``setdhd``/``singul``/``norver``; invoked
at /root/reference/src/libparmmg.c:128-204 before adaptation) and whose
parallel supplement lives in analys_pmmg.c.  The semantics reproduced here:

- boundary faces are tet faces without a neighbor (``build_adjacency``);
- an edge shared by two boundary faces whose normals make a dihedral angle
  sharper than ``angedg`` (default 45 deg) is a *ridge* (``MG_GEO``) —
  Mmg's ``setdhd``;
- an edge whose two boundary faces carry different surface references is a
  *reference edge* (``MG_REF``);
- an edge with a number of incident boundary faces other than 2 is
  *non-manifold* (``MG_NOM``, e.g. open boundaries);
- a boundary vertex with exactly 2 incident ridge edges is a ridge point
  (``MG_GEO``); with 1 or >2 it is a *corner* (``MG_CRN``) — Mmg's
  ``singul`` rules;
- vertex normals are area-weighted averages of incident boundary-face
  normals (Mmg's ``norver``; the two-normal ridge bookkeeping is carried by
  the per-face normals, recomputed on demand).

Everything is sort/segment based (no hash tables): boundary face-edge
records are matched through the unique-edge table of ``ops.edges``.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh, tet_face_vertices
from ..core.constants import (
    ANGEDG, FACE_EDGES, IDIR, MG_BDY, MG_CRN, MG_GEO, MG_NOM, MG_REF)
from .adjacency import build_adjacency
from .edges import unique_edges

# weight of the second-form fit's priors beside the fan's data, whose
# normal matrix is scaled to entries of the order of 1
RIDGE = 1e-3

_IDIR_J = jnp.asarray(IDIR)
_FACE_EDGES_J = jnp.asarray(FACE_EDGES)


class AnalysisResult(NamedTuple):
    mesh: Mesh
    vnormal: jax.Array    # [capP, 3] unit vertex normals (0 off-surface)


def boundary_vertex_normals(mesh: Mesh, lists=None) -> jax.Array:
    """[capP,3] unit outward vertex normals from true-boundary faces.

    Weighted average over incident MG_BDY (non-PARBDY) faces via ONE
    concatenated scatter — cheap enough to run inside the waves (the
    hausd-driven surface approximation needs endpoint normals per split/
    collapse candidate; Mmg instead stores xPoint normals, norver).
    Zeros off-surface.  Where the mesh carries a normal (a surface
    vertex on a frozen seam) that one stands.

    ``lists``: an ``ops/surflist.Tally`` (default: one that observes
    where the program is placed); where it is on, the scatter and the
    face geometry run over the listed (face, corner) records alone.
    """
    import jax.numpy as jnp
    from ..core.constants import IDIR, MG_BDY, MG_PARBDY, EPSD
    from . import surflist
    lists = surflist.Tally() if lists is None else lists
    capP = mesh.capP
    idir = jnp.asarray(IDIR)
    isb = ((mesh.ftag & MG_BDY) != 0) & ((mesh.ftag & MG_PARBDY) == 0) & \
        mesh.tmask[:, None]
    if lists.on:
        live = face_corner_list(isb, lists)

        def updates(p, ok):
            fc = FaceCorner(mesh, p)
            fn = jnp.cross(fc.ea, fc.eb)
            return jnp.where(ok, fc.vid, capP), fn * fc.weight[:, None]
        nacc = surflist.staged_scatter(
            jnp.zeros((capP + 1, 3), mesh.vert.dtype), live,
            updates)[:capP]
    else:
        fv = mesh.tet[:, idir]                             # [T,4,3]
        fp = mesh.vert[fv]                                 # [T,4,3,3]
        ea, eb = fp[:, :, 1] - fp[:, :, 0], fp[:, :, 2] - fp[:, :, 0]
        fn = jnp.cross(ea, eb)
        wgt = corner_weights(ea, eb)
        idx12 = jnp.concatenate(
            [jnp.where(isb[:, f], fv[:, f, k], capP)
             for f in range(4) for k in range(3)])
        pay12 = jnp.concatenate([fn[:, f] * wgt[:, f, k, None]
                                 for f in range(4) for k in range(3)])
        nacc = jnp.zeros((capP + 1, 3), mesh.vert.dtype).at[idx12].add(
            pay12, mode="drop")[:capP]
    vn = nacc / (jnp.linalg.norm(nacc, axis=-1, keepdims=True) + EPSD)
    # a frozen seam vertex's fan is cut by the seam: this mesh holds the
    # faces of one side only and their sum is tilted towards it; the
    # split carried the whole fan's normal (distribute.split_to_shards)
    return jnp.where(carries_normal(mesh)[:, None], mesh.vnrm, vn)


def face_corner_list(isb: jax.Array, lists):
    """The ``ops/surflist.Live`` list of the (face, corner) records of
    the faces ``isb`` [capT, 4], in the order of the 12-fold
    concatenation the full-width scatters use: position
    (3 f + k) capT + t for corner k of face f of tet t.  Counted into
    ``lists``."""
    from . import surflist
    live = surflist.Live(jnp.concatenate(
        [isb[:, f] for f in range(4) for _ in range(3)]))
    lists.note(live.count)
    return live


class FaceCorner:
    """What a chunk of :func:`face_corner_list` positions ``p`` [c]
    names, computed at the chunk's width: tet ``t``, face ``f``, corner
    ``k`` [c]; the face's vertex ids ``fv`` [c, 3] and points ``fp``
    [c, 3, 3]; its edge vectors ``ea``, ``eb`` [c, 3] (the normal is
    ``ea x eb``); the corner's vertex ``vid`` and its
    :func:`corner_weights` ``weight`` [c]."""

    def __init__(self, mesh: Mesh, p: jax.Array):
        from . import surflist
        j, self.t = p // mesh.capT, p % mesh.capT
        self.f, self.k = j // 3, j % 3
        self.fv = surflist.face_vertices(mesh.tet[self.t], self.f)
        self.fp = mesh.vert[self.fv]                       # [c,3,3]
        self.ea = self.fp[:, 1] - self.fp[:, 0]
        self.eb = self.fp[:, 2] - self.fp[:, 0]
        self.vid = surflist.take(self.fv, self.k)
        self.weight = surflist.take(corner_weights(self.ea, self.eb),
                                    self.k)


def corner_weights(ea: jax.Array, eb: jax.Array):
    """For triangles (p0, p1, p2) given by their edge vectors ``ea`` =
    p1 - p0 and ``eb`` = p2 - p0 [..., 3], the ones the face normal
    ``ea x eb`` is made of: [..., 3], the weight 1 / (|a|^2 |b|^2) of
    the triangle's normal at corner k, a and b its two edges there.
    Max 1999, "Weights for computing
    vertex normals from facet normals": summed with these the facet
    normals give the exact normal wherever the fan's vertices lie on a
    sphere, for any triangle shapes, where the area-weighted sum errs
    in the first order of the fan's irregularity (0.019 rad on
    sphere_mesh(16)) and every Bezier lift with it.  On a plane every
    weighting gives the plane's normal."""
    from ..core.constants import EPSD
    aa, bb = jnp.sum(ea * ea, -1), jnp.sum(eb * eb, -1)
    cc = aa + bb - 2.0 * jnp.sum(ea * eb, -1)       # |p2 - p1|^2
    return 1.0 / jnp.maximum(
        jnp.stack([aa * bb, aa * cc, bb * cc], axis=-1), EPSD)


def tangent_basis(vn: jax.Array):
    """Two unit vectors (e1, e2) [..., 3] spanning the plane normal to
    the unit vectors ``vn`` [..., 3], a function of ``vn`` alone: e1 is
    the coordinate axis ``vn`` is least along, made orthogonal to it."""
    from ..core.constants import EPSD
    axis = jax.nn.one_hot(jnp.argmin(jnp.abs(vn), axis=-1), 3,
                          dtype=vn.dtype)
    e1 = axis - jnp.sum(axis * vn, -1, keepdims=True) * vn
    e1 = e1 / (jnp.linalg.norm(e1, axis=-1, keepdims=True) + EPSD)
    return e1, jnp.cross(vn, e1)


class SecondForm(NamedTuple):
    form: jax.Array       # [capP, 3] (a, b, c) of II in (e1, e2)
    e1: jax.Array         # [capP, 3] tangent_basis of the normal given
    e2: jax.Array
    normal: jax.Array     # [capP, 3] the normal the fit corrects, unit
    spokes: jax.Array     # [capP] boundary edges of the fan (a seam
    #                       vertex's: of the faces this mesh holds)

    def along(self, t: jax.Array) -> jax.Array:
        """II(t, t) [capP] of vectors ``t`` [capP, 3]."""
        u, v = jnp.sum(t * self.e1, -1), jnp.sum(t * self.e2, -1)
        return (self.form[:, 0] * u * u + 2.0 * self.form[:, 1] * u * v
                + self.form[:, 2] * v * v)


def _second_form_moments(p: jax.Array, n: jax.Array, isb: jax.Array):
    """[T, 4, 22] the moments of :func:`boundary_second_form`'s fit that
    each tet adds at each of its corners: ``p``, ``n`` [T, 4, 3] the
    corners' points and unit normals, ``isb`` [T, 4] the tet's faces
    that make the surface."""
    from ..core.constants import EPSD
    e1, e2 = tangent_basis(n)
    # the spoke from corner k to corner j bounds the tet's faces f
    # other than k and j: as many readings as of those are surface
    nb = isb.astype(p.dtype)
    off = 1.0 - jnp.eye(4, dtype=p.dtype)
    wkj = (jnp.sum(nb, -1)[:, None, None] - nb[:, :, None]
           - nb[:, None, :]) * off                         # [T,4,4]
    d = p[:, None, :, :] - p[:, :, None, :]                # [T,k,j,3]
    ll = jnp.maximum(jnp.sum(d * d, -1), EPSD)             # [T,4,4]
    ln = jnp.sqrt(ll)
    y = -2.0 * jnp.sum(d * n[:, :, None, :], -1) / ln      # l kappa
    u = jnp.sum(d * e1[:, :, None, :], -1)
    v = jnp.sum(d * e2[:, :, None, :], -1)
    lt = jnp.sqrt(u * u + v * v) + EPSD
    u, v = u / lt, v / lt
    phi = (ln * u * u, 2.0 * ln * u * v, ln * v * v, 2.0 * u, 2.0 * v)
    mom = [phi[i] * phi[j] for i in range(5) for j in range(i, 5)] + \
        [f * y for f in phi] + [ll, jnp.ones_like(ll)]
    return jnp.sum(wkj[..., None] * jnp.stack(mom, -1), axis=2)


def boundary_second_form(mesh: Mesh, vn: jax.Array,
                         isb: jax.Array | None = None, lists=None):
    """The surface's second fundamental form at every boundary vertex,
    fitted over its fan, and the normal the fit corrects: a
    ``SecondForm`` (``form`` [capP, 3] = (a, b, c), ``e1``, ``e2``,
    ``normal`` [capP, 3], ``spokes`` [capP])
    with II(t, t) = a u^2 + 2 b u v + c v^2 for a tangent
    t = u e1 + v e2, (e1, e2) = ``tangent_basis(vn)``; ``vn`` [capP, 3]
    the unit vertex normals (outward) the caller has, ``isb`` [capT, 4]
    the faces that make the surface (default: the true-boundary faces
    of ``boundary_vertex_normals``).

    A spoke d of length l from the vertex to a fan neighbour shows the
    normal curvature of its direction, kappa = -2 d.n / l^2 (the circle
    through both points that is normal to n at the vertex: exact on a
    sphere, where every spoke reads 1 / R whatever its length; positive
    where the surface bends away from the outward normal).  A normal
    that is off by a small tangent vector delta (a weighted sum of
    facet normals is exact on a sphere only: on a torus's irregular
    fans it errs by up to 0.05 rad) adds 2 delta.t / l to the reading
    of the unit direction t, 0.6 for a spoke of 0.1 where the
    curvatures are 2.5 and 0.7.  So the fit has five unknowns, the
    form and delta: least squares over the fan's spokes of
    l kappa ~ l II(t, t) + 2 delta.t, each boundary edge counted once a
    face it bounds.  Two weak priors (RIDGE of the normal matrix, which
    is scaled to the fan's own size) say "no tilt" and "isotropic":
    they decide what a fan of fewer than five directions leaves open
    and cost a sound fan a thousandth of its anisotropy; on a sphere
    they agree with the data and the fit stays exact.  ``normal`` is
    ``vn`` + delta, unit.  The fit's error is of the order of the
    surface's third derivative times the fan's asymmetry times a
    spoke's length.  One gather of ``vn`` at the tets' corners and one
    scatter of the fit's moments; with ``lists`` on (an
    ``ops/surflist.Tally``, default: one that observes where the program
    is placed) both over the tets that hold a face of ``isb`` alone: a
    tet that holds none adds zeros.
    """
    from ..core.constants import EPSD, MG_PARBDY
    capP = mesh.capP
    if isb is None:
        isb = ((mesh.ftag & MG_BDY) != 0) & \
            ((mesh.ftag & MG_PARBDY) == 0) & mesh.tmask[:, None]
    from . import surflist
    lists = surflist.Tally() if lists is None else lists
    if lists.on:
        live = surflist.Live(mesh.tmask & jnp.any(isb, axis=1))
        lists.note(4 * live.count)

        def updates(t, ok):
            tv = mesh.tet[t]
            pay = _second_form_moments(mesh.vert[tv], vn[tv], isb[t])
            return (jnp.where(ok[:, None], tv, capP).reshape(-1),
                    pay.reshape(-1, 22))
        acc = surflist.staged_scatter(
            jnp.zeros((capP + 1, 22), mesh.vert.dtype), live,
            updates)[:capP]
    else:
        tv = mesh.tet
        pay = _second_form_moments(mesh.vert[tv], vn[tv], isb)
        idx4 = jnp.where(mesh.tmask[:, None], tv, capP).reshape(-1)
        acc = jnp.zeros((capP + 1, 22), mesh.vert.dtype).at[idx4].add(
            pay.reshape(-1, 22), mode="drop")[:capP]
    cnt = jnp.maximum(acc[:, 21], 1.0)
    # the fan's own length scale makes the five columns alike in size
    # (1 where there is no fan: zero moments then give the zero form
    # and ``vn`` back, not 0 / 0)
    scale = jnp.where(acc[:, 21] > 0, jnp.sqrt(acc[:, 20] / cnt), 1.0)
    sc = (scale, scale, scale, 1.0, 1.0)
    pairs = [(i, j) for i in range(5) for j in range(i, 5)]
    A = [[None] * 5 for _ in range(5)]
    for k, (i, j) in enumerate(pairs):
        A[i][j] = A[j][i] = acc[:, k] / (cnt * sc[i] * sc[j])
    rhs = [acc[:, 15 + i] / (cnt * sc[i]) for i in range(5)]
    # priors: isotropic ((a - c)^2 + (2 b)^2 small), no tilt
    A[0][0], A[2][2] = A[0][0] + RIDGE, A[2][2] + RIDGE
    A[0][2] = A[2][0] = A[0][2] - RIDGE
    A[1][1] = A[1][1] + 4.0 * RIDGE
    A[3][3], A[4][4] = A[3][3] + RIDGE, A[4][4] + RIDGE
    # Gaussian elimination of the SPD system, unrolled and elementwise
    # (no batched factorisation inside a wave)
    for i in range(5):
        piv = 1.0 / jnp.maximum(A[i][i], EPSD)
        for r in range(i + 1, 5):
            f = A[r][i] * piv
            for c in range(i, 5):
                A[r][c] = A[r][c] - f * A[i][c]
            rhs[r] = rhs[r] - f * rhs[i]
    x = [None] * 5
    for i in reversed(range(5)):
        acc_i = rhs[i]
        for c in range(i + 1, 5):
            acc_i = acc_i - A[i][c] * x[c]
        x[i] = acc_i / jnp.maximum(A[i][i], EPSD)
    form = jnp.stack([x[0], x[1], x[2]], -1) / scale[:, None]
    ve1, ve2 = tangent_basis(vn)
    vn1 = vn + x[3][:, None] * ve1 + x[4][:, None] * ve2
    vn1 = vn1 / (jnp.linalg.norm(vn1, axis=-1, keepdims=True) + EPSD)
    return SecondForm(form, ve1, ve2, vn1, 0.5 * acc[:, 21])


def face_depth(p: jax.Array, n: jax.Array, new_edge: jax.Array):
    """How far the surface stands from a flat triangle, as its corners'
    normals describe it: ``p`` [..., 3, 3] the corners, ``n``
    [..., 3, 3] the unit normals there, ``new_edge`` [..., 3] bool the
    edges (0-1, 1-2, 0-2) to judge beside the centroid.  The cubic
    patch through the corners that is normal to ``n`` there (Vlachos'
    PN triangle, the one a split's lift puts its midpoints on) has the
    control point (2 p_i + p_j - w_ij n_i) / 3 on the edge i-j next to
    i, w_ij = (p_j - p_i).n_i.  The patch's centre stands
    |sum_ij w_ij n_i| / 18 from the triangle's centroid (a^2 / (6 R)
    on a sphere) and the middle of the edge i-j |w_ij n_i + w_ji n_j|
    / 8 from the chord's (l^2 / (8 R): the collapse's own test of the
    edge it removes).  Returns the largest of those [...]."""
    d = p[..., None, :, :] - p[..., :, None, :]            # [..,i,j,3]
    w = jnp.sum(d * n[..., :, None, :], -1)                # [..,i,j]
    wn = w[..., None] * n[..., :, None, :]                 # w_ij n_i
    centre = jnp.linalg.norm(jnp.sum(wn, axis=(-3, -2)), axis=-1) / 18.0
    pairs = ((0, 1), (1, 2), (0, 2))
    edges = jnp.stack([jnp.linalg.norm(
        wn[..., i, j, :] + wn[..., j, i, :], axis=-1) for i, j in pairs],
        axis=-1) / 8.0
    return jnp.maximum(centre, jnp.max(
        jnp.where(new_edge, edges, 0.0), axis=-1))


def carries_normal(mesh: Mesh) -> jax.Array:
    """[capP] bool: frozen seam vertices whose surface normal the mesh
    carries (``Mesh.vnrm``) because their fan is cut by the seam."""
    from ..core.constants import MG_PARBDY
    return ((mesh.vtag & MG_PARBDY) != 0) & jnp.any(mesh.vnrm != 0, axis=-1)


def ridge_vertex_normals(mesh: Mesh):
    """Per-side normals (n1, n2) at ridge/reference-line vertices.

    The reference stores TWO normals per ridge point (xPoint n1/n2,
    routed by the hashNorver face coloring, analys_pmmg.c:199-1171 —
    faces connected without crossing the ridge share a slot).  Batched
    equivalent: per ridge vertex, the incident boundary faces are
    2-clustered by normal direction — side 1 is seeded by the largest
    incident face (two-channel scatter-max), side 2 is everything
    deviating from that seed by more than ~half the ridge angle.  Exact
    for the ubiquitous two-smooth-patch ridge; a connectivity coloring
    (the reference's) differs only on pathological multi-patch points,
    which classify MG_NOM/corner and are excluded anyway.

    Returns (n1 [capP,3], n2 [capP,3]) unit normals; zeros off-ridge.
    """
    import jax.numpy as jnp
    from ..core.constants import (IDIR, MG_BDY, MG_PARBDY, MG_GEO,
                                  MG_REF, MG_CRN, MG_NOM, EPSD)
    from .edges import PRI_MIN, tie_hash
    capP = mesh.capP
    idir = jnp.asarray(IDIR)
    is_ridge_v = mesh.vmask & ((mesh.vtag & (MG_GEO | MG_REF)) != 0) & \
        ((mesh.vtag & (MG_CRN | MG_NOM)) == 0)
    isb = ((mesh.ftag & MG_BDY) != 0) & ((mesh.ftag & MG_PARBDY) == 0) & \
        mesh.tmask[:, None]
    fv = mesh.tet[:, idir]                                  # [T,4,3]
    fp = mesh.vert[fv]
    fn = jnp.cross(fp[:, :, 1] - fp[:, :, 0], fp[:, :, 2] - fp[:, :, 0])
    area2 = jnp.linalg.norm(fn, axis=-1)                    # [T,4]
    fn_u = fn / (area2[..., None] + EPSD)
    # seed: the largest incident boundary face per ridge vertex
    rec_v = jnp.concatenate(
        [jnp.where(isb[:, f] & is_ridge_v[fv[:, f, k]], fv[:, f, k],
                   capP) for f in range(4) for k in range(3)])
    rec_s = jnp.concatenate([area2[:, f] for f in range(4)
                             for _ in range(3)])
    rec_n = jnp.concatenate([fn_u[:, f] for f in range(4)
                             for _ in range(3)])
    smax = jnp.full(capP + 1, -jnp.inf, mesh.vert.dtype).at[rec_v].max(
        rec_s, mode="drop")
    at_max = (rec_v < capP) & (rec_s >= smax[jnp.clip(rec_v, 0, capP)])
    t_ch = jnp.where(at_max, tie_hash(rec_v.shape[0]), PRI_MIN)
    tmax = jnp.full(capP + 1, PRI_MIN, jnp.int32).at[
        jnp.where(at_max, rec_v, capP)].max(t_ch, mode="drop")
    seed_sel = at_max & (t_ch == tmax[jnp.clip(rec_v, 0, capP)])
    seed = jnp.zeros((capP + 1, 3), mesh.vert.dtype).at[
        jnp.where(seed_sel, rec_v, capP)].set(
        jnp.where(seed_sel[:, None], rec_n, 0.0), mode="drop",
        unique_indices=True)[:capP]
    # side split: within ~22.5 deg of the seed = side 1, else side 2
    # (patches meeting at a ridge differ by > ANGEDG = 45 deg)
    dots = jnp.sum(rec_n * seed[jnp.clip(rec_v, 0, capP - 1)], axis=-1)
    side1 = dots >= jnp.cos(jnp.pi / 8)
    pay = jnp.concatenate(
        [jnp.where(side1[:, None], rec_n, 0.0),
         jnp.where(side1[:, None], 0.0, rec_n)], axis=1)    # [R,6]
    acc = jnp.zeros((capP + 1, 6), mesh.vert.dtype).at[rec_v].add(
        pay, mode="drop")[:capP]
    n1 = acc[:, :3] / (jnp.linalg.norm(acc[:, :3], axis=-1,
                                       keepdims=True) + EPSD)
    n2 = acc[:, 3:] / (jnp.linalg.norm(acc[:, 3:], axis=-1,
                                       keepdims=True) + EPSD)
    n1 = jnp.where(is_ridge_v[:, None], n1, 0.0)
    n2 = jnp.where(is_ridge_v[:, None], n2, 0.0)
    return n1, n2


def ridge_vertex_tangents(mesh: Mesh, et=None, lists=None) -> jax.Array:
    """[capP, 3] unit tangent of the feature (ridge/ref) line at each
    MG_GEO/MG_REF vertex; zeros elsewhere.

    The reference stores the tangent in the xPoint alongside the two
    per-side normals (Mmg norver; maintained across ranks by
    PMMG_hashNorver, analys_pmmg.c:199-1171).  Batched equivalent: the
    direction sign along a curve is arbitrary, so accumulate the OUTER
    PRODUCT of the incident special-edge directions per vertex (sign-
    free) and take the principal eigenvector by a few power iterations —
    exact for <=2 incident feature edges (the ridge-point case).

    ``lists``: an ``ops/surflist.Tally`` (default: one that observes
    where the program is placed); where it is on, the special edges are
    listed and the sum runs over them alone, first ends then second, as
    the concatenated scatter adds them.
    """
    from ..core.constants import MG_GEO, MG_REF
    from . import surflist
    lists = surflist.Tally() if lists is None else lists
    capP = mesh.capP
    if et is None:      # callers on the hot path pass their shared table
        et = unique_edges(mesh)
    special = et.emask & ((et.etag & (MG_GEO | MG_REF)) != 0)

    def outer9(va, vb):
        d = mesh.vert[vb] - mesh.vert[va]
        d = d / jnp.maximum(jnp.linalg.norm(d, axis=-1, keepdims=True),
                            1e-30)
        return d[:, :, None] * d[:, None, :]              # [E,3,3]

    if lists.on:
        live = surflist.Live(special)
        lists.note(2 * live.count)

        def end(side):
            def updates(e, ok):
                ev = jnp.clip(et.ev[e], 0, capP - 1)      # [c,2]
                return (jnp.where(ok, ev[:, side], capP),
                        outer9(ev[:, 0], ev[:, 1]).reshape(-1, 9))
            return updates
        M = jnp.zeros((capP + 1, 9), mesh.vert.dtype)
        for side in range(2):
            M = surflist.staged_scatter(M, live, end(side))
        M = M[:capP].reshape(capP, 3, 3)
    else:
        va = jnp.clip(et.ev[:, 0], 0, capP - 1)
        vb = jnp.clip(et.ev[:, 1], 0, capP - 1)
        outer = outer9(va, vb)
        pay = jnp.where(special[:, None, None], outer, 0.0).reshape(-1, 9)
        idx2 = jnp.concatenate([jnp.where(special, va, capP),
                                jnp.where(special, vb, capP)])
        M = jnp.zeros((capP + 1, 9), mesh.vert.dtype).at[idx2].add(
            jnp.concatenate([pay, pay]), mode="drop")[:capP].reshape(
            capP, 3, 3)
    has = jnp.trace(M, axis1=1, axis2=2) > 1e-12
    # principal eigenvector by power iteration (M is PSD; 4 steps are
    # plenty for the 2-edge spectrum).  Init with the column under the
    # largest diagonal entry — never orthogonal to the principal
    # direction (a fixed init like (1,1,1) is exactly orthogonal to
    # common lattice directions such as (1,-1,0)).
    diag = M[:, jnp.arange(3), jnp.arange(3)]
    jcol = jnp.argmax(diag, axis=1)
    v = jnp.take_along_axis(M, jcol[:, None, None].repeat(3, 1),
                            axis=2)[:, :, 0]
    v = jnp.where(jnp.linalg.norm(v, axis=-1, keepdims=True) > 1e-30,
                  v, 1.0)
    for _ in range(4):
        v = jnp.einsum("pij,pj->pi", M, v)
        v = v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True),
                            1e-30)
    return jnp.where(has[:, None], v, 0.0)


def face_normals(mesh: Mesh) -> jax.Array:
    """[capT, 4, 3] outward (non-unit) normals of each tet face.

    With the IDIR convention and positively oriented tets, the cross
    product of the two face edge vectors points outward.
    """
    fv = tet_face_vertices(mesh.tet)               # [T,4,3] vertex ids
    p = mesh.vert[fv]                              # [T,4,3,3]
    return jnp.cross(p[:, :, 1] - p[:, :, 0], p[:, :, 2] - p[:, :, 0])


def analyze_mesh_impl(mesh: Mesh, angedg: float = ANGEDG) -> AnalysisResult:
    """Run the full sequential surface analysis; jittable.

    Expects/It (re)builds adjacency, then derives all geometric entity tags
    from scratch (existing REQ/PARBDY bits are preserved).
    """
    mesh = build_adjacency(mesh)
    capT, capP = mesh.capT, mesh.capP
    et = unique_edges(mesh)
    capE = et.ev.shape[0]

    # open-boundary faces (-opnbdy ingestion, MG_OPNBDY): an interior
    # face pair carries the tag on BOTH slots; analysis must see the
    # sheet ONE-sided (else every sheet edge counts 4 records and the
    # whole sheet turns non-manifold) — the lower-tet-id slot represents
    # the geometric face
    from ..core.constants import MG_OPNBDY
    opn = (mesh.ftag & MG_OPNBDY) != 0
    own_side = (mesh.adja < 0) | \
        (jnp.arange(capT)[:, None] < (mesh.adja >> 2))
    is_bdy_face = ((mesh.ftag & MG_BDY) != 0) & mesh.tmask[:, None] & \
        (~opn | own_side)                                             # [T,4]
    nrm = face_normals(mesh)                                          # [T,4,3]
    nrm_unit = nrm / jnp.maximum(
        jnp.linalg.norm(nrm, axis=-1, keepdims=True), 1e-30)

    # --- boundary face-edge records (12 per tet) -------------------------
    # record r = (tet t, face f, edge j of face): eid via the edge table
    le = _FACE_EDGES_J[None, :, :]                       # [1,4,3] local edge
    le = jnp.broadcast_to(le, (capT, 4, 3))
    eid = jnp.take_along_axis(
        et.edge_id[:, None, :].repeat(4, axis=1), le, axis=2)   # [T,4,3]
    rec_valid = is_bdy_face[:, :, None] & jnp.ones((1, 1, 3), bool)
    R = capT * 12
    eid_f = eid.reshape(R)
    val_f = rec_valid.reshape(R)
    nrm_f = jnp.broadcast_to(nrm_unit[:, :, None, :],
                             (capT, 4, 3, 3)).reshape(R, 3)
    fref_f = jnp.broadcast_to(mesh.fref[:, :, None],
                              (capT, 4, 3)).reshape(R)
    opn_f = jnp.broadcast_to(opn[:, :, None], (capT, 4, 3)).reshape(R)

    # --- sort records by eid, match neighbors in segments ----------------
    key = jnp.where(val_f, eid_f, capE)
    order = jnp.argsort(key)
    ks = key[order]
    n_s = nrm_f[order]
    r_s = fref_f[order]
    v_s = val_f[order]
    eq_next = (ks[1:] == ks[:-1]) & (ks[:-1] < capE)
    same_next = jnp.concatenate([eq_next, jnp.array([False])])
    same_prev = jnp.concatenate([jnp.array([False]), eq_next])
    idx = jnp.arange(R)
    partner = jnp.where(same_next, idx + 1,
                        jnp.where(same_prev, idx - 1, idx))
    # per-record pair tests (meaningful only when the segment has size 2;
    # larger segments are non-manifold and flagged by the count below).
    # Open-boundary sheets are unoriented (the representative slot's
    # normal sign is arbitrary): their dihedral test uses |dot|.
    o_s = opn_f[order]
    dot = jnp.sum(n_s * n_s[partner], axis=-1)
    dot = jnp.where(o_s | o_s[partner], jnp.abs(dot), dot)
    ridge_r = v_s & (same_next | same_prev) & (dot < angedg)
    refed_r = v_s & (same_next | same_prev) & (r_s != r_s[partner])

    # segment sizes per eid (number of incident boundary faces)
    cnt = jnp.zeros(capE + 1, jnp.int32).at[
        jnp.where(val_f, eid_f, capE)].add(1, mode="drop")[:capE]
    has_bdy = cnt > 0
    nom_e = has_bdy & (cnt != 2)

    # scatter pair flags to unique edges
    ridge_e = jnp.zeros(capE + 1, bool).at[
        jnp.where(v_s, ks, capE)].max(ridge_r, mode="drop")[:capE]
    refed_e = jnp.zeros(capE + 1, bool).at[
        jnp.where(v_s, ks, capE)].max(refed_r, mode="drop")[:capE]
    ridge_e = ridge_e & ~nom_e      # non-manifold handled separately
    bdy_e = has_bdy

    # --- write edge tags back onto every tet-edge slot -------------------
    add = (jnp.where(ridge_e, MG_GEO, 0) | jnp.where(refed_e, MG_REF, 0)
           | jnp.where(nom_e, MG_NOM, 0)
           | jnp.where(bdy_e, MG_BDY, 0)).astype(jnp.uint32)
    etag = mesh.etag | jnp.where(mesh.tmask[:, None], add[et.edge_id],
                                 jnp.uint32(0))

    # --- vertex classification (singul) ----------------------------------
    sing_e = ridge_e | refed_e | nom_e       # edges that make points special
    nsing = jnp.zeros(capP + 1, jnp.int32)
    vbdy = jnp.zeros(capP + 1, bool)
    vnom = jnp.zeros(capP + 1, bool)
    vref = jnp.zeros(capP + 1, bool)
    for side in range(2):
        tgt = jnp.where(et.emask, et.ev[:, side], capP)
        nsing = nsing.at[tgt].add(sing_e.astype(jnp.int32), mode="drop")
        vbdy = vbdy.at[tgt].max(bdy_e, mode="drop")
        vnom = vnom.at[tgt].max(nom_e, mode="drop")
        vref = vref.at[tgt].max(refed_e, mode="drop")
    nsing, vbdy = nsing[:capP], vbdy[:capP]
    vnom, vref = vnom[:capP], vref[:capP]

    on_ridge = nsing == 2
    corner = (nsing == 1) | (nsing > 2)
    vadd = (jnp.where(vbdy, MG_BDY, 0)
            | jnp.where(on_ridge, MG_GEO, 0)
            | jnp.where(corner, MG_CRN, 0)
            | jnp.where(vnom, MG_NOM, 0)
            | jnp.where(vref, MG_REF, 0)).astype(jnp.uint32)
    vtag = jnp.where(mesh.vmask, mesh.vtag | vadd, mesh.vtag)

    # --- vertex normals (norver) -----------------------------------------
    fv = tet_face_vertices(mesh.tet)                       # [T,4,3]
    acc = jnp.zeros((capP + 1, 3), mesh.vert.dtype)
    nrm_flat = nrm.reshape(capT * 4, 3)       # area-weighted (non-unit)
    for c in range(3):
        tgt = jnp.where(is_bdy_face, fv[:, :, c], capP).reshape(-1)
        acc = acc.at[tgt].add(nrm_flat, mode="drop")
    vn = acc[:capP]
    vn = vn / jnp.maximum(jnp.linalg.norm(vn, axis=-1, keepdims=True), 1e-30)
    vn = jnp.where(vbdy[:, None], vn, 0.0)

    out = dataclasses.replace(mesh, etag=etag, vtag=vtag)
    return AnalysisResult(out, vn)


# Always jitted: eager dispatch of the ~200-op analysis graph is
# catastrophic over a remote-device transport (one RPC per op); under jit
# it is one compiled executable (cached persistently).  jit-of-jit at the
# call sites inside other jitted code simply inlines.
analyze_mesh = jax.jit(analyze_mesh_impl)
