"""The torus domain and generator of ``torus-shock-aniso``
(``domains/torus.py``, ``meshes/torus.py``) on synthetic triangles whose
verdict is known, and the three readers PR 34 added, each on a hand-made
``run``: a value, and None where its source is absent.  The cell's own
job is in test_torus_job.py."""
import json
import os

import numpy as np
import pytest

import checker
from byname import load
from inputs import boundary_vertices, build_input

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "torus-shock-aniso.json")) as f:
    CONFIG = json.load(f)
DOMAIN = CONFIG["domain"]
HAUSD = CONFIG["options"]["dparam"]["hausd"]
torus = load("domains", "torus")
R, r = DOMAIN["R"], DOMAIN["r"]


def on_torus(theta, phi, lift=0.0):
    """Points at angle ``theta`` round the tube and ``phi`` along the
    ring, ``lift`` outside the surface along its normal."""
    theta, phi = np.asarray(theta, float), np.asarray(phi, float)
    rho = R + (r + lift) * np.cos(theta)
    return np.stack([rho * np.cos(phi), rho * np.sin(phi),
                     (r + lift) * np.sin(theta)], axis=-1)


def triangle(theta0, dtheta, dphi, lift=(0.0, 0.0, 0.0)):
    """A triangle with its corners on the torus round (theta0, 0): two
    of them ``dtheta`` apart round the tube, the third ``dphi`` along
    the ring; each then moved out by ``lift``."""
    th = theta0 + np.array([-0.5 * dtheta, 0.5 * dtheta, 0.0])
    ph = np.array([0.0, 0.0, dphi])
    return on_torus(th, ph, np.asarray(lift))[None]


def on(tri, tol=1e-9):
    return bool(torus.on_surface(tri, DOMAIN, tol)[0])


def test_the_tolerances_are_the_jobs_promise():
    assert DOMAIN["chord_tol"] == HAUSD == 0.01
    assert DOMAIN["vertex_tol"] < HAUSD
    assert DOMAIN["volume"] == pytest.approx(2 * np.pi ** 2 * R * r * r,
                                             rel=1e-12)


@pytest.mark.parametrize("theta0", [0.0, np.pi], ids=["outer", "inner"])
def test_triangles_on_and_off_the_torus(theta0):
    # 0.1 round the tube: its chord sags 0.1^2 / (8 r) = 3.1e-3
    fine = triangle(theta0, 0.25, 0.05)
    assert on(fine)
    # the caller's tol is a box's: it changes nothing here
    assert on(fine, tol=0.0) and on(fine, tol=1.0)
    vertex, chord = torus.deviations(fine, DOMAIN)
    assert vertex.max() < 1e-12 and 1e-3 < chord[0] < 3.2e-3
    # one vertex off by twice the tolerance, outside and inside
    for lift in (2 * DOMAIN["vertex_tol"], -2 * DOMAIN["vertex_tol"]):
        assert not on(triangle(theta0, 0.25, 0.05, lift=(lift, 0, 0)))
        assert not on(triangle(theta0, 0.25, 0.05, lift=(0, 0, lift)))
    # a chord of 0.32 round the tube: corners on the torus, its middle
    # 0.032 under it, its centroid two thirds of that
    coarse = triangle(theta0, 0.8, 0.05)
    vertex, chord = torus.deviations(coarse, DOMAIN)
    assert vertex.max() < 1e-12 and chord[0] > 1.5 * HAUSD
    assert not on(coarse)


def test_a_chord_along_the_ring_counts_on_both_halves():
    """Along the ring the curvature is cos(theta) / rho: 0.71 on the
    outer equator, where a flat triangle stands INSIDE the solid, and
    -1.67 on the inner one, where it stands outside; the distance counts
    the same.  The same angle along the ring, 0.25, is a chord of 0.35
    on the outer equator and of 0.15 on the inner: both within hausd;
    at 0.55 the inner one (0.33 long) is not."""
    outer = on_torus([0.0, 0.0, 0.1], [0.0, 0.25, 0.125])[None]
    inner = on_torus([np.pi, np.pi, np.pi - 0.1],
                     [0.0, 0.55, 0.275])[None]
    assert on(on_torus([np.pi, np.pi, np.pi - 0.1],
                       [0.0, 0.25, 0.125])[None])
    d_out = torus.deviations(outer, DOMAIN)[1][0]
    d_in = torus.deviations(inner, DOMAIN)[1][0]
    assert 0.3 * HAUSD < d_out < HAUSD and on(outer)
    assert d_in > HAUSD and not on(inner)
    # signed: the centroids lie on opposite sides of the surface
    side = lambda t: np.hypot(np.hypot(*t.mean(1)[0, :2]) - R,  # noqa: E731
                              t.mean(1)[0, 2]) - r
    assert side(outer) < 0 < side(inner)
    # another centre
    moved = dict(DOMAIN, centre=[1.0, -2.0, 0.5])
    assert bool(torus.on_surface(outer + moved["centre"], moved, 0)[0])
    assert not bool(torus.on_surface(outer, moved, 0)[0])


def test_the_generator_is_the_programs_fixture():
    pytest.importorskip("jax")
    from parmmg_tpu.utils.fixtures import torus_mesh
    args = CONFIG["mesh"]["args"]
    vert, tet = load("meshes", "torus").build(**args)
    want_v, want_t = torus_mesh(args["nu"], args["nc"], args["R"], args["r"])
    assert np.array_equal(vert, want_v) and np.array_equal(tet, want_t)
    assert tet.dtype == np.int32 and len(tet) == 23040 and len(vert) == 4860
    assert (checker.volumes(vert[tet]) > 0).all()
    uniq, cnt = checker.face_counts(tet)
    assert set(cnt) == {1, 2} and (cnt == 1).sum() == 3840
    skin = boundary_vertices(tet, len(vert))
    assert skin.sum() == 1920
    assert torus.distance(vert[skin], DOMAIN).max() < 1e-12
    assert checker.volumes(vert[tet]).sum() == pytest.approx(
        DOMAIN["volume"], rel=0.01)


def test_the_seed_moves_the_interior_and_turns_a_few_tets_over():
    a, b = build_input(CONFIG, 2147483659), build_input(CONFIG, 2147483659)
    assert np.array_equal(a["vert"], b["vert"])
    base, tet = load("meshes", "torus").build(**CONFIG["mesh"]["args"])
    skin = boundary_vertices(tet, len(base))
    assert np.array_equal(a["vert"][skin], base[skin])
    moved = np.abs(a["vert"] - base)[~skin]
    assert 0 < moved.max() <= CONFIG["mesh"]["jitter"]
    assert a["met"].shape == (len(base), 6)
    # the section's corner cells are thin: the input is not a valid
    # mesh, and the job has to hand back none of them
    assert 1 <= (checker.volumes(a["vert"][tet]) <= 0).sum() <= 40


# ---- the readers ---------------------------------------------------------
def run_of(*jobs):
    return {"setup_s": 1.0, "jobs": list(jobs), "chips": 1, "trace": None,
            "peaks": None, "window_compiles": 0}


def job(counters, phases=None):
    return {"counters": counters, "phases": phases or {}, "spans": []}


def test_bound_share():
    read = load("layer_metrics", "bound_share").read
    assert read(run_of(
        job({"surf.bound_verts": 1920.0, "surf.bdy_verts": 1920.0}),
        job({"surf.bound_verts": 960.0, "surf.bdy_verts": 1920.0}))) == 75.0
    assert read(run_of(job({"surf.bound_verts": 0.0,
                            "surf.bdy_verts": 1538.0}))) == 0.0
    # the parent: no ``surf.bdy_verts``; a cube: no regular vertex seen
    assert read(run_of(job({"surf.bound_verts": 0.0}))) is None
    assert read(run_of(job({"surf.bound_verts": 0.0,
                            "surf.bdy_verts": 0.0}))) is None
    assert read(run_of(job({}))) is None


def test_bound_s():
    read = load("layer_metrics", "bound_s").read
    assert read(run_of(job({}, {"hausd bound": 0.25, "metric": 0.5}),
                       job({}, {"hausd bound": 0.75}))) == 0.5
    assert read(run_of(job({}, {"metric": 0.5}))) is None


def test_veto_share():
    read = load("layer_metrics", "veto_share").read
    assert read(run_of(
        job({"surf.hveto": 10.0, "adapt.ncollapse": 1990.0}),
        job({"surf.hveto": 30.0, "adapt.ncollapse": 1970.0}))) == 1.0
    assert read(run_of(job({"surf.hveto": 0.0,
                            "adapt.ncollapse": 5.0}))) == 0.0
    assert read(run_of(job({"adapt.ncollapse": 5.0}))) is None
    assert read(run_of(job({"surf.hveto": 0.0,
                            "adapt.ncollapse": 0.0}))) is None
