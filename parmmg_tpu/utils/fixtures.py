"""Synthetic mesh generators for tests and benchmarks.

The reference test suite pulls Cube/Sphere/Torus meshes from a separate data
repo (cmake/testing/pmmg_tests.cmake:12-23); we generate equivalents
procedurally so the test matrix is self-contained.
"""
from __future__ import annotations

import numpy as np

# Each unit cube cell is split into 6 tets (Kuhn/Freudenthal triangulation:
# all tets share the main diagonal (0,0,0)-(1,1,1); produces a conforming
# mesh across cells without parity flips).
_KUHN_TETS = np.array([
    [0, 1, 3, 7],
    [0, 1, 5, 7],
    [0, 2, 3, 7],
    [0, 2, 6, 7],
    [0, 4, 5, 7],
    [0, 4, 6, 7],
], dtype=np.int64)
# corner i of the cell has offsets (i&1, (i>>1)&1, (i>>2)&1)


def cube_mesh(n: int = 4):
    """Structured [0,1]^3 cube: (n+1)^3 vertices, 6*n^3 tets.

    Returns (vert [np,3] float64, tet [ne,4] int32), positively oriented.
    """
    k = n + 1
    g = np.arange(k) / n
    X, Y, Z = np.meshgrid(g, g, g, indexing="ij")
    vert = np.stack([X.ravel(), Y.ravel(), Z.ravel()], axis=1)

    def vid(i, j, l):
        return (i * k + j) * k + l

    ii, jj, ll = np.meshgrid(np.arange(n), np.arange(n), np.arange(n),
                             indexing="ij")
    base = np.stack([ii.ravel(), jj.ravel(), ll.ravel()], 1)  # [n^3,3]
    corners = np.empty((base.shape[0], 8), np.int64)
    for c in range(8):
        off = np.array([c & 1, (c >> 1) & 1, (c >> 2) & 1])
        q = base + off
        corners[:, c] = vid(q[:, 0], q[:, 1], q[:, 2])
    tet = corners[:, _KUHN_TETS].reshape(-1, 4)
    tet = _orient_positive(vert, tet)
    return vert, tet.astype(np.int32)


def sphere_mesh(n: int = 8):
    """Unit ball: cube mesh mapped radially onto the ball (graded)."""
    vert, tet = cube_mesh(n)
    c = vert * 2.0 - 1.0                       # [-1,1]^3
    linf = np.max(np.abs(c), axis=1)
    l2 = np.linalg.norm(c, axis=1)
    scale = np.where(l2 > 1e-12, linf / np.maximum(l2, 1e-12), 1.0)
    vert = c * scale[:, None]
    tet = _orient_positive(vert, tet)
    return vert, tet.astype(np.int32)


def torus_mesh(nu: int = 12, nc: int = 4, R: float = 1.0, r: float = 0.4):
    """Solid torus: centerline radius R, tube radius r.

    Square-to-disk mapped cross-section (nc cells across), extruded around
    nu stations with periodic Kuhn cells — conforming across the wrap by
    translation invariance of the Freudenthal split.  The genus-1 boundary
    (Euler characteristic 0) is the fixture the reference CI matrix pulls
    from its mesh repo (cmake/testing/pmmg_tests.cmake:25-38).

    The 4 nc nu boundary vertices lie on the torus exactly and the
    section's four corners leave no crease.  The square-to-disk map
    flattens the cells at those corners: at nu = 60, nc = 8 (23,040
    tets) the thinnest have a volume of 5.4e-6, a twenty-fifth of the
    median's 1.3e-4; 180 are under a twentieth of it and 540 under a
    tenth, all in the four corner columns.  None is inverted, but a
    jitter of the interior vertices by a twentieth of a cell (0.005)
    turns 9 to 13 of them over.
    """
    kc = nc + 1
    g = np.arange(kc) / nc * 2.0 - 1.0
    A, B = np.meshgrid(g, g, indexing="ij")
    ab = np.stack([A.ravel(), B.ravel()], axis=1)
    linf = np.max(np.abs(ab), axis=1)
    l2 = np.linalg.norm(ab, axis=1)
    scale = np.where(l2 > 1e-12, linf / np.maximum(l2, 1e-12), 1.0)
    disk = ab * scale[:, None] * r                 # [(nc+1)^2, 2]
    vert = []
    for u in np.arange(nu) / nu * 2.0 * np.pi:
        x = (R + disk[:, 0]) * np.cos(u)
        y = (R + disk[:, 0]) * np.sin(u)
        vert.append(np.stack([x, y, disk[:, 1]], axis=1))
    vert = np.concatenate(vert)

    def vid(i, j, l):
        return (i % nu) * (kc * kc) + j * kc + l

    ii, jj, ll = np.meshgrid(np.arange(nu), np.arange(nc), np.arange(nc),
                             indexing="ij")
    base = np.stack([ii.ravel(), jj.ravel(), ll.ravel()], 1)
    corners = np.empty((base.shape[0], 8), np.int64)
    for c in range(8):
        off = np.array([c & 1, (c >> 1) & 1, (c >> 2) & 1])
        q = base + off
        corners[:, c] = vid(q[:, 0], q[:, 1], q[:, 2])
    tet = corners[:, _KUHN_TETS].reshape(-1, 4)
    tet = _orient_positive(vert, tet)
    return vert, tet.astype(np.int32)


def _orient_positive(vert, tet):
    p = vert[tet]
    det = np.einsum("ti,ti->t", p[:, 1] - p[:, 0],
                    np.cross(p[:, 2] - p[:, 0], p[:, 3] - p[:, 0]))
    flip = det < 0
    tet = tet.copy()
    tet[flip, 0], tet[flip, 1] = tet[flip, 1], tet[flip, 0].copy()
    return tet


def analytic_iso_metric(vert: np.ndarray, kind: str = "uniform",
                        h: float = 0.1):
    """Test metrics: uniform h, or a planar 'shock' refinement band."""
    if kind == "uniform":
        return np.full(vert.shape[0], h)
    if kind == "shock":
        # small size near the plane x=0.5, large away (aniso-torus analogue
        # of the reference CI matrix)
        d = np.abs(vert[:, 0] - 0.5)
        return h * (0.2 + 4.0 * d)
    raise ValueError(kind)


def analytic_ani_metric(vert: np.ndarray, kind: str = "shock",
                        h: float = 0.1, h_tan: float = 0.45):
    """Packed anisotropic test metrics [n, 6] (Mmg packing
    m11,m12,m13,m22,m23,m33): ``shock`` = planar-shock tensor — tight
    spacing ACROSS the plane x=0.5 (h scaled by distance, like the iso
    shock), loose ``h_tan`` along the tangential directions.  The
    aniso-torus analogue of the reference CI matrix
    (cmake/testing/pmmg_tests.cmake:31-38)."""
    n = vert.shape[0]
    if kind == "shock":
        d = np.abs(vert[:, 0] - 0.5)
        hx = h * (0.2 + 4.0 * d)
        m = np.zeros((n, 6))
        m[:, 0] = 1.0 / hx ** 2
        m[:, 3] = 1.0 / h_tan ** 2
        m[:, 5] = 1.0 / h_tan ** 2
        return m
    raise ValueError(kind)


def cylinder_mesh(n: int = 6, r: float = 0.5):
    """Solid cylinder (radius r, height 1, axis z): cube mesh with the
    (x, y) square cross-section mapped onto the disk.  The cap rims are
    CURVED ridge lines (90-degree dihedral along a circle) — the
    feature-line fixture class (torus-equator/cylinder-cap) the
    reference CI exercises for ridge geometry."""
    vert, tet = cube_mesh(n)
    c = vert[:, :2] * 2.0 - 1.0
    linf = np.max(np.abs(c), axis=1)
    l2 = np.linalg.norm(c, axis=1)
    scale = np.where(l2 > 1e-12, linf / np.maximum(l2, 1e-12), 1.0)
    vert = np.concatenate([c * scale[:, None] * r, vert[:, 2:]], axis=1)
    tet = _orient_positive(vert, tet)
    return vert, tet.astype(np.int32)


def steady_state_migration_scenario(niter: int = 4, cycles: int = 2,
                                    n_shards: int = 2,
                                    n_devices: int | None = None,
                                    return_all: bool = False):
    """The compile-governor CI scenario, shared by the --ledger budget
    gate (scripts/ledger_check.py) and the tier-1 regression test
    (tests/test_compile_ledger.py) so the two gates cannot drift apart:
    ``niter`` migration iterations over a small cube whose interface
    sizes drift every iteration — the steady-state loop whose retag /
    extend-ids / flood / interface-check entry points must stay on a
    bounded set of compiled variants.  ``n_devices`` < ``n_shards``
    runs the grouped (G>1) composition, exercising the grouped
    analysis/halo entry points on the same bucketed shapes.

    Returns the adapted merged mesh, or (mesh, met, part) with
    ``return_all`` — the shared fixture the burned-down migration tests
    assert conformity/labels on, so tier-1 pays ONE compile for the
    whole scenario family instead of one per test."""
    import jax.numpy as jnp
    from ..core.mesh import make_mesh
    from ..ops.analysis import analyze_mesh
    from ..parallel import dist

    vert, tet = cube_mesh(2)
    m = make_mesh(vert, tet, capP=6 * len(vert), capT=6 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, 0.4, m.vert.dtype)
    out, met_m, part = dist.distributed_adapt_multi(
        m, met, n_shards, niter=niter, cycles=cycles,
        n_devices=n_devices)
    return (out, met_m, part) if return_all else out
