"""[0,1]^3 in (n+1)^3 vertices and 6 n^3 tets (the yardstick's copy of
``parmmg_tpu/utils/fixtures.cube_mesh``).  Each unit cell is split into 6
tets sharing the main diagonal (Kuhn/Freudenthal): conforming across
cells without parity flips."""
import numpy as np

_KUHN_TETS = np.array([[0, 1, 3, 7], [0, 1, 5, 7], [0, 2, 3, 7],
                       [0, 2, 6, 7], [0, 4, 5, 7], [0, 4, 6, 7]], np.int64)


def build(n: int):
    k = n + 1
    g = np.arange(k) / n
    vert = np.stack([a.ravel() for a in
                     np.meshgrid(g, g, g, indexing="ij")], 1)
    cell = np.stack([a.ravel() for a in np.meshgrid(
        np.arange(n), np.arange(n), np.arange(n), indexing="ij")], 1)
    corners = np.empty((len(cell), 8), np.int64)
    for c in range(8):
        q = cell + np.array([c & 1, (c >> 1) & 1, (c >> 2) & 1])
        corners[:, c] = (q[:, 0] * k + q[:, 1]) * k + q[:, 2]
    tet = corners[:, _KUHN_TETS].reshape(-1, 4)
    p = vert[tet]
    flip = np.einsum("ij,ij->i", p[:, 1] - p[:, 0], np.cross(
        p[:, 2] - p[:, 0], p[:, 3] - p[:, 0])) < 0
    tet[flip, 0], tet[flip, 1] = tet[flip, 1], tet[flip, 0].copy()
    return vert, tet.astype(np.int32)
