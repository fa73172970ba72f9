"""Curved-geometry workloads: hausd-driven surface approximation on the
sphere, torus topology preservation, and the {1,2,4,8}-device matrix —
the reference CI shape (cmake/testing/pmmg_tests.cmake:25-150) with
quality-asserting gates instead of exit codes.

What the surface machinery does on a curved patch (the grouped path's
own cases are in test_sphere_grouped.py): a split boundary edge's
midpoint is lifted onto the Bezier curve through its endpoints and
their normals, which are exact on a sphere (Max's corner weights) and,
at a vertex on a group seam, the whole fan's; and a regular surface
vertex slides in its tangent plane like one on a flat patch and is put
back onto the surface by the curvature its fan shows (ops/smooth.py),
while the step stays within hausd of the old surface."""
import numpy as np
import jax.numpy as jnp
import pytest

from parmmg_tpu.core import constants as C
from parmmg_tpu.core.mesh import make_mesh, tet_volumes
from parmmg_tpu.ops.adapt import adapt_mesh
from parmmg_tpu.ops.adjacency import build_adjacency, check_adjacency
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.quality import tet_quality
from parmmg_tpu.utils.fixtures import sphere_mesh, torus_mesh


def _adapted_sphere(hausd, hsiz=0.2, n=5):
    vert, tet = sphere_mesh(n)
    m = make_mesh(vert, tet, capP=8 * len(vert), capT=8 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, hsiz)
    m, met, _ = adapt_mesh(m, met, hausd=hausd)
    return m


def _bdy_radial_dev(m):
    vm = np.asarray(m.vmask)
    vtag = np.asarray(m.vtag)
    bdy = vm & ((vtag & C.MG_BDY) != 0)
    rr = np.linalg.norm(np.asarray(m.vert)[bdy], axis=1)
    return np.abs(rr - 1.0).max()


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_sphere_hausd_keeps_surface_on_sphere():
    """With hausd, refined boundary points are lifted onto the Bezier
    surface: the radial deviation stays within a few hausd, and is far
    smaller than the chord-midpoint deviation of the hausd-off run
    (h~0.45 chords on the unit sphere sag ~h^2/8 ~ 0.025)."""
    hausd = 0.01
    m_on = _adapted_sphere(hausd)
    dev_on = _bdy_radial_dev(m_on)
    m_off = _adapted_sphere(None)
    dev_off = _bdy_radial_dev(m_off)
    assert dev_off > 0.012            # the off-run really sags
    assert dev_on <= 3.0 * hausd
    assert dev_on < 0.5 * dev_off
    m_on = build_adjacency(m_on)
    assert check_adjacency(m_on) == {"asymmetric": 0, "face_mismatch": 0}
    vols = np.asarray(tet_volumes(m_on))[np.asarray(m_on.tmask)]
    assert (vols > 0).all()
    # the lifted surface hugs the unit ball: volume within 5% of 4pi/3
    assert abs(vols.sum() - 4.1888) < 0.05 * 4.1888


@pytest.mark.parametrize("ngroups", [1, 3])
def test_hausd_metric_bound_refines_curved_boundary(ngroups):
    """The defsiz route: even a very coarse size request refines curved
    boundaries to sqrt(8*hausd/kappa) (unit sphere: kappa=1).  In a
    group of a split mesh (``ngroups`` 3) the bound reads the same
    curvature at every vertex it reaches: the frozen seam vertices are
    skipped with the edges that leave them, and the others' normals
    are exact on a sphere whatever the shapes of their fans."""
    from parmmg_tpu.ops.metric import hausd_metric_bound
    vert, tet = sphere_mesh(5)
    m = make_mesh(vert, tet, capP=2 * len(vert), capT=2 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, 1.5)                   # "no refinement please"
    if ngroups > 1:
        import jax
        from parmmg_tpu.parallel.distribute import split_to_shards
        from parmmg_tpu.parallel.partition import (fix_contiguity,
                                                   morton_partition)
        part = fix_contiguity(tet, morton_partition(
            vert[tet].mean(axis=1), ngroups))
        stacked, met_s = split_to_shards(m, met, part, ngroups)
        m = jax.tree.map(lambda a: a[0], stacked)
        met = met_s[0]
    met2 = hausd_metric_bound(m, met, hausd=0.005, hmin=1e-3)
    mh = np.asarray(met2)
    vtag = np.asarray(m.vtag)
    vm = np.asarray(m.vmask)
    reg_bdy = vm & ((vtag & C.MG_BDY) != 0) & \
        ((vtag & (C.MG_GEO | C.MG_CRN | C.MG_PARBDY)) == 0)
    target = np.sqrt(8 * 0.005 / 1.0)             # = 0.2
    assert reg_bdy.sum() > 20
    assert np.median(mh[reg_bdy]) < 1.5 * target
    # the curvature is the sphere's at every vertex the bound reaches,
    # next to a seam too: none is refined far past the target
    assert mh[reg_bdy].min() > 0.8 * target
    # interior sizes untouched
    interior = vm & ((vtag & C.MG_BDY) == 0)
    assert (mh[interior] == 1.5).all()


def _bdy_euler(m):
    """Euler characteristic of the boundary surface (V - E + F)."""
    tm = np.asarray(m.tmask)
    tet = np.asarray(m.tet)[tm]
    ftag = np.asarray(m.ftag)[tm]
    tris = []
    for f in range(4):
        sel = (ftag[:, f] & C.MG_BDY) != 0
        tris.append(np.sort(tet[sel][:, C.IDIR[f]], axis=1))
    tris = np.unique(np.concatenate(tris), axis=0)
    V = len(np.unique(tris.reshape(-1)))
    edges = np.unique(np.sort(np.concatenate(
        [tris[:, [0, 1]], tris[:, [1, 2]], tris[:, [0, 2]]]), axis=1),
        axis=0)
    return V - len(edges) + len(tris)


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_torus_adapt_preserves_topology_and_quality():
    vert, tet = torus_mesh(nu=16, nc=4)
    m = make_mesh(vert, tet, capP=5 * len(vert), capT=5 * len(tet))
    m = analyze_mesh(m).mesh
    assert _bdy_euler(m) == 0                      # genus 1
    vol0 = float(np.asarray(tet_volumes(m))[np.asarray(m.tmask)].sum())
    met = jnp.full(m.capP, 0.3)
    m, met, st = adapt_mesh(m, met, hausd=0.01)
    m = build_adjacency(m)
    assert check_adjacency(m) == {"asymmetric": 0, "face_mismatch": 0}
    assert _bdy_euler(m) == 0                      # still a torus
    vols = np.asarray(tet_volumes(m))[np.asarray(m.tmask)]
    assert (vols > 0).all()
    assert abs(vols.sum() - vol0) < 0.06 * vol0
    q = np.asarray(tet_quality(m))[np.asarray(m.tmask)]
    assert q.min() > 0.05


