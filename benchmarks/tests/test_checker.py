"""The plain reference on meshes whose verdict is known."""
import json
import os

import numpy as np
import pytest

import checker
from inputs import build_input

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "cube-shock-iso.json")) as f:
    CONFIG = json.load(f)
# the seeded cube at a size a test holds; its own tet count and edge
# share stand in for the bands read from the real scale
SMALL = dict(CONFIG, mesh={"generator": "cube", "args": {"n": 6},
                           "jitter": 0.05 / 6})
GUARANTEES = dict(CONFIG["guarantees"], ntets={"band": [1296, 1296]},
                  len_ok_share={"band": [0.0, 100.0]})


def bf16(a):
    """``a`` rounded to bfloat16 (nearest even), as float64."""
    u = np.asarray(a, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.view(np.float32).astype(np.float64)


@pytest.fixture(scope="module")
def good():
    inp = build_input(SMALL, 2147483653)        # a seed past 2**31
    return inp["vert"].astype(np.float32).astype(np.float64), \
        inp["tet"], inp["met"]


def verdict(vert, tet, met, degraded=0):
    numbers = checker.measure(vert, tet, met, CONFIG["domain"])
    numbers["degraded"] = degraded
    rows = checker.judge(numbers, GUARANTEES)
    return all(r["ok"] for r in rows), {r["name"] for r in rows
                                        if not r["ok"]}, numbers


def test_good_mesh_passes(good):
    ok, failed, numbers = verdict(*good)
    assert ok, failed
    assert numbers["coord_bits"] >= 20
    assert numbers["volume_rel_err"] < 1e-6
    assert 0.0 < numbers["qmin"] <= numbers["qmean"] <= 1.0


def test_hole_fails(good):
    vert, tet, met = good
    inner = np.all((vert[tet] > 0.2) & (vert[tet] < 0.8), axis=(1, 2))
    ok, failed, _ = verdict(vert, np.delete(tet, np.where(inner)[0][0], 0),
                            met)
    assert not ok and "unmatched_interior_faces" in failed


def test_inverted_tet_fails(good):
    vert, tet, met = good
    tet = tet.copy()
    tet[7, [0, 1]] = tet[7, [1, 0]]
    ok, failed, _ = verdict(vert, tet, met)
    assert not ok and "inverted_tets" in failed and "qmin" in failed


def test_lower_precision_storage_fails(good):
    """The same mesh with its coordinates carried in bfloat16 (and in
    float16) is refused, whatever else it still satisfies."""
    vert, tet, met = good
    ok, failed, numbers = verdict(bf16(vert), tet, met)
    assert not ok and "coord_bits" in failed
    assert numbers["coord_bits"] <= 8
    half = vert.astype(np.float16).astype(np.float64)
    ok, failed, numbers = verdict(half, tet, met)
    assert not ok and "coord_bits" in failed
    assert numbers["coord_bits"] <= 11


def test_degraded_job_fails(good):
    ok, failed, _ = verdict(*good, degraded=1)
    assert not ok and failed == {"degraded"}


@pytest.mark.parametrize("breakage", ["index", "nan", "no_metric",
                                      "negative_size", "no_tets"])
def test_unmeasurable_output_is_broken(good, breakage):
    vert, tet, met = (a.copy() for a in good)
    if breakage == "index":
        tet[0, 0] = len(vert)
    elif breakage == "nan":
        vert[3, 1] = np.nan
    elif breakage == "no_metric":
        met = None
    elif breakage == "negative_size":
        met[5] = -1.0
    else:
        tet = tet[:0]
    ok, failed, numbers = verdict(vert, tet, met)
    assert not ok and numbers["broken"] == 1 and "broken" in failed


def test_wrong_size_mesh_leaves_the_band(good):
    vert, tet, met = good
    numbers = checker.measure(vert, tet, met, CONFIG["domain"])
    numbers["degraded"] = 0
    rows = checker.judge(numbers, dict(GUARANTEES,
                                       ntets={"band": [1400, 1500]}))
    assert [r["name"] for r in rows if not r["ok"]] == ["ntets"]


def test_edge_lengths_follow_mmg():
    p0 = np.zeros((3, 3))
    p1 = np.array([[1.0, 0, 0], [0, 2.0, 0], [0, 0, 0.5]])
    # iso: constant size h gives d / h; sizes 1 and e give d (e-1)/e
    ln = checker.edge_lengths(p0, p1, np.array([0.5, 2.0, 1.0]),
                              np.array([0.5, 2.0, np.e]))
    assert np.allclose(ln, [2.0, 1.0, 0.5 * (np.e - 1) / np.e])
    # aniso: identical endpoint tensors give sqrt(e^T M e)
    m = np.tile([4.0, 0, 0, 1.0, 0, 0.25], (3, 1))
    assert np.allclose(checker.edge_lengths(p0, p1, m, m), [2.0, 2.0, 0.25])


def test_quality_is_one_on_the_regular_tet():
    reg = np.array([[[1, 1, 1], [1, -1, -1], [-1, 1, -1], [-1, -1, 1]]],
                   float)
    if checker.quality(reg)[0] < 0:
        reg = reg[:, [1, 0, 2, 3]]
    assert np.isclose(checker.quality(reg)[0], 1.0)
    # the same tet stretched by 2 along x is regular in diag(1/4, 1, 1)
    stretched = reg * [2.0, 1.0, 1.0]
    m = np.array([[0.25, 0, 0, 1.0, 0, 1.0]])
    assert np.isclose(checker.quality(stretched, m)[0], 1.0)
    assert checker.quality(stretched)[0] < 0.9
