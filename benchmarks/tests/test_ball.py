"""The ball domain and the sphere cell's reference (``checker.py`` with
``domains/ball.py``) on synthetic triangles and balls whose verdict is
known; the cell's own job is in test_sphere_job.py."""
import json
import os

import numpy as np
import pytest

import checker
from byname import load
from inputs import build_input

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "sphere-sizemap-iso.json")) as f:
    CONFIG = json.load(f)
DOMAIN = CONFIG["domain"]
ball = load("domains", "ball")


def triangle(side, lift=(0.0, 0.0, 0.0)):
    """An equilateral triangle of that side with its corners on the unit
    sphere round the pole, each then moved out along its radius."""
    r = side / 3 ** 0.5
    ang = 2 * np.pi * np.arange(3) / 3
    p = np.stack([r * np.cos(ang), r * np.sin(ang),
                  np.full(3, (1 - r * r) ** 0.5)], axis=1)
    return (p * (1 + np.asarray(lift))[:, None])[None]


def test_triangles_on_and_off_the_sphere():
    on = lambda tri, tol=1e-9: bool(ball.on_surface(tri, DOMAIN, tol)[0])
    fine = triangle(0.05)           # its chord sags 0.05^2 / 6 = 4e-4
    assert on(fine)
    # the caller's tol is a box's: it changes nothing here
    assert on(fine, tol=0.0) and on(fine, tol=1.0)
    vertex, chord = ball.deviations(fine, DOMAIN)
    assert vertex.max() < 1e-12 and abs(chord[0] - 0.05 ** 2 / 6) < 1e-5
    # one vertex that was not lifted: off by twice the tolerance
    assert not on(triangle(0.05, lift=(2 * DOMAIN["vertex_tol"], 0, 0)))
    assert not on(triangle(0.05, lift=(0, -2 * DOMAIN["vertex_tol"], 0)))
    # a flat chord over a coarse patch: its corners are on the sphere,
    # its middle is 0.3^2 / 6 = 0.015 under it
    coarse = triangle(0.3)
    assert ball.deviations(coarse, DOMAIN)[0].max() < 1e-12
    assert not on(coarse)
    # another centre and radius
    moved = dict(DOMAIN, centre=[1.0, -2.0, 0.5], radius=3.0)
    tri = 3.0 * triangle(0.03) + np.asarray(moved["centre"])
    assert bool(ball.on_surface(tri, moved, 1e-9)[0])
    assert not bool(ball.on_surface(tri, DOMAIN, 1e-9)[0])


# A ball a test holds: the generator at n = 8 is coarser than any output
# of the cell, so its own flat faces sag more than the cell's chord_tol
# (0.39^2 / 6 under the sphere); the chord limit stands in at that scale,
# the vertex limit is the configuration's.  No jitter: the radial map's
# thinnest tets, along the cube's diagonals, are what a jitter turns over
SMALL = dict(CONFIG, mesh={"generator": "sphere", "args": {"n": 8},
                           "jitter": 0.0})
SMALL_DOMAIN = dict(DOMAIN, chord_tol=0.03)
GUARANTEES = dict(CONFIG["guarantees"], ntets={"band": [3072, 3072]},
                  len_ok_share={"band": [0.0, 100.0]},
                  volume_rel_err={"max": 0.05})


def verdict(vert, tet, met, domain=SMALL_DOMAIN):
    numbers = checker.measure(vert, tet, met, domain)
    numbers["degraded"] = 0
    rows = checker.judge(numbers, GUARANTEES)
    return {r["name"] for r in rows if not r["ok"]}, numbers


@pytest.fixture(scope="module")
def small():
    inp = build_input(SMALL, 2147483653)
    return inp["vert"].astype(np.float32).astype(np.float64), \
        inp["tet"], inp["met"]


def test_a_conforming_ball_passes(small):
    failed, numbers = verdict(*small)
    assert failed == set(), failed
    assert numbers["unmatched_interior_faces"] == 0
    # the inscribed polyhedron lacks about area x mean sag of the volume
    assert 0.005 < numbers["volume_rel_err"] < 0.05
    # under the cell's own chord limit the coarse skin is what it is
    failed, numbers = verdict(*small, domain=DOMAIN)
    assert "unmatched_interior_faces" in failed
    assert numbers["unmatched_interior_faces"] > 300


def test_a_dropped_tet_is_not_correct(small):
    vert, tet, met = small
    inner = np.linalg.norm(vert[tet], axis=2).max(axis=1) < 0.7
    failed, _ = verdict(vert, np.delete(tet, np.where(inner)[0][0], 0), met)
    assert "unmatched_interior_faces" in failed
    # one with a face on the skin: its three inner faces, each with a
    # vertex of the shell below, are left without a partner
    r = np.sort(np.linalg.norm(vert[tet], axis=2), axis=1)
    skin = (r[:, 1] > 0.99) & (r[:, 0] < 0.9)
    failed, _ = verdict(vert, np.delete(tet, np.where(skin)[0][0], 0), met)
    assert "unmatched_interior_faces" in failed


def test_chord_midpoints_on_the_surface_are_not_correct():
    """The ball at n = 8 refined to the grid of n = 16 with every new
    point BETWEEN the old ones (the trilinear image of the coarse grid):
    conforming, positively oriented, and its new surface points are the
    bare midpoints of the coarse chords, about 0.01 under the sphere."""
    fine_v, fine_t = load("meshes", "cube").build(16)
    coarse_v, _ = load("meshes", "sphere").build(8)
    grid = coarse_v.reshape(9, 9, 9, 3)
    ijk = np.rint(fine_v * 16).astype(int)
    lo, odd = ijk // 2, (ijk % 2).astype(float)
    hi = np.minimum(lo + 1, 8)
    vert = np.zeros_like(fine_v)
    for cx in (0, 1):
        for cy in (0, 1):
            for cz in (0, 1):
                w = np.prod(np.where([cx, cy, cz], 0.5 * odd,
                                     1 - 0.5 * odd), axis=1)
                pick = np.where([cx, cy, cz], hi, lo)
                vert += w[:, None] * grid[pick[:, 0], pick[:, 1], pick[:, 2]]
    assert (checker.volumes(vert[fine_t]) > 0).all()
    met = np.full(len(vert), 0.2)
    failed, numbers = verdict(vert, fine_t, met)
    assert "unmatched_interior_faces" in failed
    assert numbers["inverted_tets"] == 0 and numbers["overfull_faces"] == 0
    # the same connectivity with every surface point ON the sphere passes
    sphere_v, sphere_t = load("meshes", "sphere").build(16)
    assert np.array_equal(np.sort(sphere_t, axis=1), np.sort(fine_t, axis=1))
    failed, _ = verdict(sphere_v, sphere_t, met)
    assert failed == {"ntets"}      # the band is the small ball's count
