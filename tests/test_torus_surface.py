"""A tensor metric on a surface whose curvature depends on the direction:
the pieces ``torus-shock-aniso`` rests on, each against a float64 numpy
reference written here.

- ``ops/metric.metric_intersection``: the simultaneous reduction of two
  tensors, never coarser than either;
- ``ops/analysis.boundary_second_form`` through
  ``ops/metric.hausd_metric_bound``: the curvature's tensor at the
  regular boundary vertices of ``torus_mesh`` against the torus's own
  (1 / r round the tube, cos(theta) / rho along the ring, of the other
  sign on the inner half);
- ``ops/smooth.smooth_wave``'s curved slide: the normal step follows the
  direction of the step.

The torus: ring radius R, tube radius r, a point at angle theta round
the tube (0 on the outer equator) and distance rho = R + r cos(theta)
from the axis.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from parmmg_tpu.core import constants as C
from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.metric import (clamp_metric, hausd_metric_bound,
                                   metric_intersection)
from parmmg_tpu.ops.smooth import smooth_wave
from parmmg_tpu.utils.fixtures import cube_mesh, sphere_mesh, torus_mesh

R, r = 1.0, 0.4
HAUSD = 0.01
HMIN, HMAX = 3e-3, 8.0


def sym(m6):
    m6 = np.asarray(m6, np.float64)
    return m6[..., [0, 1, 2, 1, 3, 4, 2, 4, 5]].reshape(m6.shape[:-1] + (3, 3))


def pack(m):
    return np.stack([m[..., 0, 0], m[..., 0, 1], m[..., 0, 2],
                     m[..., 1, 1], m[..., 1, 2], m[..., 2, 2]], -1)


def random_spd(rng, n, lo, hi):
    """Tensors with sizes drawn log-uniformly from [lo, hi] along the
    axes of a random rotation."""
    q, _ = np.linalg.qr(rng.normal(size=(n, 3, 3)))
    h = np.exp(rng.uniform(np.log(lo), np.log(hi), (n, 3)))
    return np.einsum("nij,nj,nkj->nik", q, 1.0 / h ** 2, q)


def reference_intersection(ma, mb):
    """The intersection by the textbook route, one tensor at a time: the
    eigenvectors p of ma^-1 mb, scaled so that p^T ma p = 1, reduce both
    (ma = P^-T P^-1, mb = P^-T diag(mu) P^-1); keep the larger of 1 and
    mu along each."""
    out = np.empty_like(ma)
    for k in range(len(ma)):
        mu, p = np.linalg.eig(np.linalg.solve(ma[k], mb[k]))
        mu, p = mu.real, p.real
        p = p / np.sqrt(np.einsum("ij,ik,kj->j", p, ma[k], p))
        pinv = np.linalg.inv(p)
        out[k] = pinv.T @ np.diag(np.maximum(mu, 1.0)) @ pinv
    return out


def test_intersection_against_the_reference_and_never_coarser():
    """To 1e-5 of the tensor's norm (float64 on both sides: rounding is
    1e-12, a result carried through bfloat16 misses by 4e-3), and in 64
    random directions the length it asks for is at most what either
    argument asks for."""
    rng = np.random.default_rng(34)
    ma = random_spd(rng, 200, 0.02, 2.0)
    mb = random_spd(rng, 200, 0.02, 2.0)
    got, finer = metric_intersection(ma, mb)
    want = reference_intersection(ma, mb)
    scale = np.linalg.norm(want, axis=(1, 2))
    assert (np.linalg.norm(got - want, axis=(1, 2)) <= 1e-5 * scale).all()
    dirs = rng.normal(size=(64, 3))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    quad = lambda m: np.einsum("di,nij,dj->nd", dirs, m, dirs)  # noqa: E731
    assert (quad(got) >= quad(ma) * (1 - 1e-9)).all()
    assert (quad(got) >= quad(mb) * (1 - 1e-9)).all()
    # a tensor that is finer everywhere is kept, and says so
    same, finer = metric_intersection(ma, 1e-3 * ma)
    assert not finer.any()
    assert np.allclose(same, ma, rtol=1e-12, atol=0)
    assert metric_intersection(ma, 4.0 * ma)[1].all()


# ---------------------------------------------------------------------------
# the curvature's tensor on the torus
# ---------------------------------------------------------------------------
def torus_frame(v):
    """Outward normal, the tangents round the tube and along the ring,
    and the two principal curvatures at points ``v`` [n, 3] on (or
    near) the torus."""
    rho = np.maximum(np.hypot(v[:, 0], v[:, 1]), 1e-30)
    tube = np.maximum(np.hypot(rho - R, v[:, 2]), 1e-30)
    ct, st = (rho - R) / tube, v[:, 2] / tube
    nrm = np.stack([ct * v[:, 0] / rho, ct * v[:, 1] / rho, st], 1)
    ring = np.stack([-v[:, 1] / rho, v[:, 0] / rho, np.zeros(len(v))], 1)
    return nrm, np.cross(nrm, ring), ring, np.full(len(v), 1.0 / r), ct / rho


def analytic_bound(v, user):
    """What ``hausd_metric_bound`` owes at surface points ``v``: the
    user's tensors [n, 3, 3] intersected with the torus's curvature
    tensor, float64."""
    nrm, t_tube, t_ring, k_tube, k_ring = torus_frame(v)
    floor = 1.0 / HMAX ** 2
    curv = floor * nrm[:, :, None] * nrm[:, None, :]
    for t, k in ((t_tube, k_tube), (t_ring, k_ring)):
        lam = np.maximum(np.abs(k) / (8.0 * HAUSD), floor)
        curv = curv + lam[:, None, None] * t[:, :, None] * t[:, None, :]
    return reference_intersection(user, curv)


def analysed_torus(nu, nc):
    vert, tet = torus_mesh(nu, nc, R, r)
    m = analyze_mesh(make_mesh(vert, tet)).mesh
    vtag = np.asarray(m.vtag)
    on = np.asarray(m.vmask) & ((vtag & C.MG_BDY) != 0)
    assert on.sum() == 4 * nc * nu and not (vtag & C.MG_GEO).any()
    return m, on


def curvature_error(got, want):
    """The difference of two tensor fields [n, 3, 3] (spectral norm) as
    a curvature, 8 hausd times it, over the tube's 1 / r: where the
    curvature's tensor leads, by how much of the largest curvature the
    one that was read misses the torus's."""
    return np.linalg.norm(got - want, ord=2, axis=(1, 2)) * 8.0 * HAUSD * r


@pytest.fixture(scope="module")
def bounds():
    """(size error per surface vertex, census) of the bound at a coarse
    and at a fine torus under a seeded tensor that is coarse on the
    surface: 0.3 to 1.5, any axes."""
    out = []
    for nu, nc in ((30, 4), (60, 8)):
        m, on = analysed_torus(nu, nc)
        rng = np.random.default_rng(nu)
        user = np.tile(np.eye(3), (m.capP, 1, 1))
        user[:len(on)][on] = random_spd(rng, int(on.sum()), 0.3, 1.5)
        met = clamp_metric(jnp.asarray(pack(user), jnp.float32), HMIN, HMAX)
        census = {}
        got = hausd_metric_bound(m, met, HAUSD, HMIN, HMAX, census=census)
        v = np.asarray(m.vert, np.float64)[on]
        want = analytic_bound(v, sym(np.asarray(met))[on])
        out.append({"err": curvature_error(sym(np.asarray(got))[on], want),
                    "census": census, "got": np.asarray(got),
                    "met": np.asarray(met), "on": on})
    return out


def test_the_bound_is_the_toruss_curvature_tensor(bounds):
    """The tensor the bound returns against the float64 one (the user's
    tensor intersected with the torus's own curvature tensor), as a
    curvature and in units of the tube's 1 / r.  The fit's error is
    first order in the fan's size (the surface's third derivative times
    the fan's asymmetry times a spoke's length): the worst vertex reads
    6.2 % where a cell is 0.21 x 0.20 and 3.3 % where it is 0.105 x 0.1,
    the medians 1.9 % and 1.2 %; the limits are 8 % and 4 %, and halve
    with the cell.  Read against them (my runs, PR 34): one curvature
    for both directions (the mean) misses by 83 % at both sizes, median
    51 %; coordinates carried in bfloat16 into the fit (a spoke's height
    of 0.003 to 0.0125 is under their rounding of 0.004) by 18 % and
    97 %, medians 5.2 % and 14 %.  The fit's RESULT rounded to bfloat16
    reads 6.3 % / 3.5 % and passes: the limits hold the arithmetic, not
    the storage of three curvatures."""
    coarse, fine = bounds
    assert coarse["err"].max() < 0.08, coarse["err"].max()
    assert fine["err"].max() < 0.04, fine["err"].max()
    assert np.median(coarse["err"]) < 0.025, np.median(coarse["err"])
    assert np.median(fine["err"]) < 0.015, np.median(fine["err"])
    for b in bounds:
        n = int(b["on"].sum())
        changed = (b["got"] != b["met"]).any(axis=1)
        # every regular surface vertex was examined and, the user's
        # tensor being coarse, changed; nothing off the surface was
        assert b["census"]["bdy_verts"] == n
        assert changed[b["on"]].all() and not changed[~b["on"]].any()
        assert abs(b["census"]["kappa_max"] - 1.0 / r) < 0.05 / r


def test_a_fine_tensor_is_kept_to_the_bit_and_sizes_keep_their_numbers():
    m, on = analysed_torus(30, 4)
    fine = jnp.asarray(pack(np.tile(np.eye(3) / 0.05 ** 2, (m.capP, 1, 1))),
                       jnp.float32)
    got = hausd_metric_bound(m, fine, HAUSD, HMIN, HMAX)
    assert np.array_equal(np.asarray(got), np.asarray(fine))
    # the scalar branch: the largest curvature an edge shows, as before
    sizes = hausd_metric_bound(m, jnp.full(m.capP, 1.5), HAUSD, HMIN)
    sizes = np.asarray(sizes)
    assert (sizes[~on] == 1.5).all()
    target = np.sqrt(8 * HAUSD * r)                 # 0.179 round the tube
    assert np.abs(sizes[on] / target - 1.0).max() < 0.05


# ---------------------------------------------------------------------------
# the slide
# ---------------------------------------------------------------------------
def torus_distance(v):
    return np.abs(np.hypot(np.hypot(v[:, 0], v[:, 1]) - R, v[:, 2]) - r)


def slide(mesh, met, waves):
    for w in range(waves):
        mesh = smooth_wave(mesh, met, wave=w, hausd=HAUSD).mesh
    return np.asarray(mesh.vert, np.float64)


def scalar_slide_error(v_old, v_new):
    """What the slide this one replaced left: one curvature for every
    direction, the mean of the two principal ones, so a step of length s
    in the unit direction t missed the torus by
    |II(t, t) - (k1 + k2) / 2| s^2 / 2."""
    nrm, t_tube, t_ring, k1, k2 = torus_frame(v_old)
    d = v_new - v_old
    a, b = np.einsum("ij,ij->i", d, t_tube), np.einsum("ij,ij->i", d, t_ring)
    return np.abs(k1 * a * a + k2 * b * b
                  - 0.5 * (k1 + k2) * (a * a + b * b)) / 2.0


def test_a_slide_on_the_torus_follows_its_direction():
    """Four waves on ``torus_mesh(30, 4)`` (cells of 0.21 x 0.20) under a
    size that lets the surface vertices travel: steps of up to 0.09.
    The moved vertices stay within 8e-4 of the torus on the inner half
    and on the outer one.  What is left is the cubic term of the
    surface (a third derivative of about 4 times s^3 / 6, 5e-4 at 0.09)
    and the quartic one (s^4 / (8 r^3), 1.3e-4): the readings are 5.9e-4
    and 2.7e-4.  One curvature for every direction misses by
    |k_tube - k_ring| s^2 / 4: the same steps under that slide stand
    over the limit on both halves (its readings at the parent: 2.0e-3
    and 1.3e-3)."""
    m, on = analysed_torus(30, 4)
    old = np.asarray(m.vert, np.float64)
    new = slide(m, jnp.full(m.capP, 0.2), 4)
    step = np.linalg.norm(new - old, axis=1)
    moved = on & (step > 0)
    inner = np.hypot(old[:, 0], old[:, 1]) < R
    assert (moved & inner).sum() > 30 and (moved & ~inner).sum() > 30
    assert step[moved].max() > 0.06
    dist = torus_distance(new)
    scalar = scalar_slide_error(old, new)
    for half in (inner, ~inner):
        assert dist[moved & half].max() < 8e-4, dist[moved & half].max()
        assert scalar[moved & half].max() > 8e-4
    # nothing else on the surface moved
    assert np.array_equal(new[on & ~moved], old[on & ~moved])


def test_a_slide_on_the_sphere_and_on_a_plane():
    """On a sphere the form is isotropic and every spoke reads 1 / R:
    the slide is exact to s^4 / 8 (2e-5 at the steps taken); on a plane
    it is the identity on the normal coordinate, to the bit."""
    vert, tet = sphere_mesh(8)
    m = analyze_mesh(make_mesh(vert, tet)).mesh
    on = (np.asarray(m.vtag) & C.MG_BDY) != 0
    old = np.asarray(m.vert, np.float64)
    new = slide(m, jnp.full(m.capP, 0.3), 2)
    step = np.linalg.norm(new - old, axis=1)
    moved = on & (step > 0)
    assert moved.sum() > 20 and step[moved].max() > 0.04
    off = np.abs(np.linalg.norm(new[moved], axis=1) - 1.0)
    assert off.max() < 1.5 * step[moved].max() ** 4 / 8 + 1e-6, off.max()
    vert, tet = cube_mesh(4)
    rng = np.random.default_rng(5)
    face = (vert[:, 2] == 0) & (vert[:, :2] > 0).all(1) & \
        (vert[:, :2] < 1).all(1)
    vert[face, :2] += rng.uniform(-0.08, 0.08, (int(face.sum()), 2))
    m = analyze_mesh(make_mesh(vert, tet)).mesh
    new = slide(m, jnp.full(m.capP, 0.25), 2)[:len(vert)]
    assert (np.abs(new[face] - vert[face]).max(axis=1) > 0).any()
    assert (new[face, 2] == 0).all()
