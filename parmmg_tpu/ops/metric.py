"""Metric synthesis, clamping and gradation.

Reference semantics: Mmg computes a size map for ``-optim`` (local mean edge
length) / ``-hsiz`` (constant), clamps to [hmin, hmax], and enforces size
gradation ``-hgrad`` (bounded relative growth along edges).  ParMmg forwards
these per group (API_functions_pmmg.c:531-830) and rejects some combos in
``PMMG_check_inputData`` (libparmmg.c:55-101).  Here each is a vectorized
kernel over the whole vertex array; gradation is an iterated scatter-min
relaxation (a parallel fixpoint instead of Mmg's sequential edge sweeps).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh, tet_edge_vertices
from ..core.constants import EPSD, HGRAD_DEFAULT


def metric_hsiz(mesh: Mesh, hsiz: float) -> jax.Array:
    """Constant target size (Mmg -hsiz)."""
    return jnp.full(mesh.capP, hsiz, mesh.vert.dtype)


def metric_optim(mesh: Mesh) -> jax.Array:
    """Local mean incident-edge length per vertex (Mmg -optim).

    Preserves the existing sizing of the mesh: adaptation then only
    improves quality without refining/coarsening on average.
    """
    ev = tet_edge_vertices(mesh.tet).reshape(-1, 2)
    p0 = mesh.vert[ev[:, 0]]
    p1 = mesh.vert[ev[:, 1]]
    l = jnp.sqrt(jnp.maximum(jnp.sum((p1 - p0) ** 2, -1), 0.0))
    w = jnp.repeat(mesh.tmask, 6).astype(mesh.vert.dtype)
    acc = jnp.zeros(mesh.capP + 1, mesh.vert.dtype)
    cnt = jnp.zeros(mesh.capP + 1, mesh.vert.dtype)
    for side in range(2):
        idx = jnp.where(jnp.repeat(mesh.tmask, 6), ev[:, side], mesh.capP)
        acc = acc.at[idx].add(l * w, mode="drop")
        cnt = cnt.at[idx].add(w, mode="drop")
    h = acc[:-1] / jnp.maximum(cnt[:-1], 1.0)
    return jnp.where(mesh.vmask, h, 1.0)


def hausd_metric_bound(mesh: Mesh, met, hausd: float, hmin: float,
                       hmax: float = float("inf"),
                       census: dict | None = None):
    """Bound boundary sizes by the surface approximation tolerance.

    The Mmg ``defsiz`` route for -hausd: a chord of length h on a surface
    of curvature kappa deviates by ~ h^2 * kappa / 8, so keeping the
    deviation under hausd requires h <= sqrt(8 * hausd / kappa).
    Ridge/corner endpoints are excluded — their normals are multivalued
    and ridges are preserved by tags, not size.  Host-side, once per run.

    Sizes (``met`` [capP]): vertex curvature is estimated from the
    spread of boundary-vertex normals over incident regular boundary
    edges, the largest an edge shows.

    Tensors (``met`` [capP, 6]): the curvature depends on the direction,
    and so does the bound.  The fan's second fundamental form
    (analysis.boundary_second_form) has principal curvatures kappa_1,
    kappa_2 along tangents t_1, t_2; the curvature's tensor asks
    ``max(|kappa_i| / (8 hausd), 1 / hmax^2)``, capped at 1 / hmin^2,
    along t_i and 1 / hmax^2 along the normal, and the result is its
    intersection with the user's tensor (``metric_intersection``: never
    coarser than either in any direction), clamped to [hmin, hmax] like
    every tensor.  A vertex the curvature asks nothing finer of keeps
    its tensor to the bit.

    ``census``, if given, receives ``bdy_verts``, the regular boundary
    vertices examined, and ``kappa_max``, the largest curvature read
    at one of them.
    """
    import numpy as np
    from ..core.constants import (
        IDIR, MG_BDY, MG_CRN, MG_GEO, MG_NOM, MG_PARBDY, MG_REQ)
    from .analysis import boundary_vertex_normals
    if census is None:
        census = {}
    census.update(bdy_verts=0, kappa_max=0.0)
    vtag = np.asarray(mesh.vtag)
    sing = MG_GEO | MG_CRN | MG_REQ | MG_PARBDY | MG_NOM
    regular = np.asarray(mesh.vmask) & ((vtag & MG_BDY) != 0) & \
        ((vtag & sing) == 0)
    census["bdy_verts"] = int(regular.sum())
    if met.ndim == 2:
        if not regular.any():
            return met
        form, e1, e2, nrm = (np.asarray(a, np.float64)[regular]
                             for a in _fan_second_form(mesh)[:4])
        kap, rot = np.linalg.eigh(np.stack(
            [form[:, [0, 1]], form[:, [1, 2]]], axis=1))   # [n,2],[n,2,2]
        census["kappa_max"] = float(np.abs(kap).max())
        tdir = rot[:, 0, :, None] * e1[:, None, :] + \
            rot[:, 1, :, None] * e2[:, None, :]            # [n,2,3]
        floor = 1.0 / hmax ** 2
        lam = np.clip(np.abs(kap) / (8.0 * hausd), floor, 1.0 / hmin ** 2)
        curv = np.einsum("ni,nij,nik->njk", lam, tdir, tdir) + \
            floor * nrm[:, :, None] * nrm[:, None, :]
        from .quality import unpack_sym
        both, finer = metric_intersection(
            np.asarray(unpack_sym(met), np.float64)[regular], curv)
        if not finer.any():
            return met
        rows = np.where(regular)[0][finer]
        b = both[finer]
        packed = np.stack([b[:, 0, 0], b[:, 0, 1], b[:, 0, 2],
                           b[:, 1, 1], b[:, 1, 2], b[:, 2, 2]], -1)
        packed = clamp_metric(jnp.asarray(packed, met.dtype), hmin, hmax)
        return met.at[jnp.asarray(rows)].set(packed)
    vn = np.asarray(boundary_vertex_normals(mesh))
    tm = np.asarray(mesh.tmask)
    tet = np.asarray(mesh.tet)[tm]
    ftag = np.asarray(mesh.ftag)[tm]
    capP = mesh.capP
    tris = []
    for f in range(4):
        sel = (ftag[:, f] & MG_BDY) != 0
        if sel.any():
            tris.append(tet[sel][:, IDIR[f]])
    if not tris:
        return met
    tris = np.concatenate(tris)
    ed = np.concatenate([tris[:, [0, 1]], tris[:, [1, 2]],
                         tris[:, [0, 2]]])
    ed = ed[regular[ed[:, 0]] & regular[ed[:, 1]]]
    if not len(ed):
        return met
    vh = np.asarray(mesh.vert)
    dn = np.linalg.norm(vn[ed[:, 0]] - vn[ed[:, 1]], axis=1)
    dl = np.linalg.norm(vh[ed[:, 0]] - vh[ed[:, 1]], axis=1)
    kappa = dn / np.maximum(dl, 1e-30)
    kv = np.zeros(capP)
    np.maximum.at(kv, ed[:, 0], kappa)
    np.maximum.at(kv, ed[:, 1], kappa)
    census["kappa_max"] = float(kv.max())
    with np.errstate(divide="ignore"):
        h_geom = np.sqrt(8.0 * hausd / np.maximum(kv, 1e-30))
    h_geom = np.maximum(np.where(kv > 1e-12, h_geom, np.inf), hmin)
    return jnp.minimum(met, jnp.asarray(h_geom, met.dtype))


@jax.jit
def _fan_second_form(mesh: Mesh):
    """The fans' second forms from the mesh's own vertex normals: one
    program a mesh shape, where the eager calls are eighty."""
    from .analysis import boundary_second_form, boundary_vertex_normals
    return boundary_second_form(mesh, boundary_vertex_normals(mesh))


def metric_intersection(ma, mb, rtol: float = 1e-6):
    """The intersection of SPD tensors ``ma`` [n, 3, 3] with symmetric
    positive semi-definite ``mb`` [n, 3, 3] by simultaneous reduction
    (Mmg's ``MMG5_intersecmet``), in numpy float64: in the basis P that
    makes ``ma`` the identity and ``mb`` diagonal (ma = L L^T, L^-1 mb
    L^-T = Q diag(mu) Q^T, P^-T = L Q) the intersection is
    P^-T diag(max(1, mu)) P^-1.  Its unit ball lies inside both unit
    balls: it asks for a length no larger than either in any direction.
    Returns (the tensors [n, 3, 3], [n] bool: ``mb`` asks for something
    finer than ``ma`` in some direction, mu > 1 + rtol; where it does
    not, the tensor is ``ma`` up to rounding)."""
    import numpy as np
    L = np.linalg.cholesky(ma)
    Li = np.linalg.inv(L)
    mu, Q = np.linalg.eigh(Li @ mb @ np.swapaxes(Li, 1, 2))
    B = L @ Q
    out = np.einsum("nij,nj,nkj->nik", B, np.maximum(mu, 1.0), B)
    return out, mu[:, -1] > 1.0 + rtol


def clamp_metric(met: jax.Array, hmin: float, hmax: float) -> jax.Array:
    if met.ndim == 1:
        return jnp.clip(met, hmin, hmax)
    # aniso: clamp eigenvalues of each tensor to [1/hmax^2, 1/hmin^2]
    from .quality import unpack_sym
    M = unpack_sym(met)
    w, V = jnp.linalg.eigh(M)
    w = jnp.clip(w, 1.0 / hmax**2, 1.0 / hmin**2)
    Mc = jnp.einsum("...ij,...j,...kj->...ik", V, w, V)
    return jnp.stack([Mc[..., 0, 0], Mc[..., 0, 1], Mc[..., 0, 2],
                      Mc[..., 1, 1], Mc[..., 1, 2], Mc[..., 2, 2]], -1)


def gradation(mesh: Mesh, met: jax.Array, hgrad: float = HGRAD_DEFAULT,
              max_sweeps: int = 20) -> jax.Array:
    """Bound relative size growth along edges (Mmg -hgrad, iso only).

    Rule (Mmg MMG5_grad2met flavor): along an edge of euclidean length d,
    h_b may not exceed h_a + (hgrad - 1) * d.  Enforced by Jacobi
    scatter-min sweeps until stationary (bounded by max_sweeps); each sweep
    is one fused gather/scatter — the parallel form of Mmg's sequential
    edge relaxation.
    """
    if met.ndim != 1:
        return met  # aniso gradation is a later milestone
    ev = tet_edge_vertices(mesh.tet).reshape(-1, 2)
    valid = jnp.repeat(mesh.tmask, 6)
    p0 = mesh.vert[ev[:, 0]]
    p1 = mesh.vert[ev[:, 1]]
    d = jnp.sqrt(jnp.maximum(jnp.sum((p1 - p0) ** 2, -1), 0.0))
    slope = hgrad - 1.0

    def sweep(met, _):
        h0 = met[ev[:, 0]]
        h1 = met[ev[:, 1]]
        cap0 = h1 + slope * d                 # bound on h at endpoint 0
        cap1 = h0 + slope * d
        out = met
        big = jnp.inf
        lim = jnp.full(met.shape[0] + 1, big, met.dtype)
        lim = lim.at[jnp.where(valid, ev[:, 0], met.shape[0])].min(
            cap0, mode="drop")
        lim = lim.at[jnp.where(valid, ev[:, 1], met.shape[0])].min(
            cap1, mode="drop")
        return jnp.minimum(met, lim[:-1]), None

    met, _ = jax.lax.scan(sweep, met, None, length=max_sweeps)
    return met
