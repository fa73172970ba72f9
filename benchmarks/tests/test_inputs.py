"""The input generators: conforming, positively oriented, seeded."""
import numpy as np
import pytest

import checker
from byname import load
from inputs import boundary_vertices, build_input, metric_at


@pytest.mark.parametrize("n", [1, 4, 7])
def test_the_cube_is_conforming_and_positive(n):
    vert, tet = load("meshes", "cube").build(n)
    assert len(tet) == 6 * n ** 3 and tet.dtype == np.int32
    assert len(vert) == (n + 1) ** 3
    vol = checker.volumes(vert[tet])
    assert (vol > 0).all() and np.isclose(vol.sum(), 1.0)
    uniq, cnt = checker.face_counts(tet)
    assert cnt.max() == 2 and (cnt == 1).sum() == 12 * n * n
    on = load("domains", "box").on_surface(
        vert[uniq[cnt == 1]], {"lo": [0, 0, 0], "hi": [1, 1, 1]}, 1e-9)
    assert on.all()


def test_a_name_with_no_file_is_refused():
    with pytest.raises(SystemExit):
        load("meshes", "no-such-mesh")
    with pytest.raises(SystemExit):
        load("meshes", "../checker")


def test_the_cube_at_the_cells_size():
    vert, tet = load("meshes", "cube").build(16)
    assert (len(vert), len(tet)) == (4913, 24576)


def test_the_seed_moves_interior_vertices_only_and_repeats():
    config = {"mesh": {"generator": "cube", "args": {"n": 4},
                       "jitter": 0.05 / 4},
              "metric": {"kind": "ani_shock",
                         "args": {"h": 0.2, "h_tan": 0.45}}}
    base, tet = load("meshes", "cube").build(4)
    a, b = build_input(config, 2147483653), build_input(config, 2147483653)
    c = build_input(config, 7)
    assert np.array_equal(a["vert"], b["vert"])
    assert not np.array_equal(a["vert"], c["vert"])
    moved = np.any(a["vert"] != base, axis=1)
    assert np.array_equal(moved, ~boundary_vertices(tet, len(base)))
    assert moved.sum() == 27 and np.abs(a["vert"] - base).max() <= 0.05 / 4
    assert a["met"].shape == (125, 6)
    # the tensor is the iso shock's size across the plane, h_tan along it
    iso = metric_at({"kind": "iso_shock", "args": {"h": 0.2}}, a["vert"])
    assert np.allclose(a["met"][:, 0], 1 / iso ** 2)
    assert np.allclose(a["met"][:, [3, 5]], 1 / 0.45 ** 2)
    # and the input itself is a mesh the checker accepts as conforming
    numbers = checker.measure(a["vert"], a["tet"], a["met"],
                              {"kind": "box", "lo": [0, 0, 0],
                               "hi": [1, 1, 1], "volume": 1})
    assert numbers["unmatched_interior_faces"] == 0
    assert numbers["inverted_tets"] == 0
