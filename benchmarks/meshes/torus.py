"""The solid torus, centreline radius ``R`` and tube radius ``r``, in
nu (nc+1)^2 vertices and 6 nu nc^2 tets (the yardstick's own copy of
``parmmg_tpu/utils/fixtures.torus_mesh``): a square section of nc x nc
cells mapped onto the disk, |x|_inf -> |x|_2 on every ring, and carried
round ``nu`` stations in periodic Kuhn cells, conforming across the wrap.
The 4 nc nu surface vertices lie on the torus exactly, and the section's
four corners leave no crease.  Positively oriented.  The map flattens
the cells at the corners of the section: at nu = 60, nc = 8 the thinnest
tets have a volume of 5.4e-6, a twenty-fifth of the median's 1.3e-4 (180
of the 23,040 under a twentieth of it, 540 under a tenth), and a seeded
jitter of the interior vertices by 0.005 (``inputs.py``) turns 9 to 13
of them over: the job has to hand back none."""
import numpy as np

from byname import load


def build(nu: int, nc: int, R: float = 1.0, r: float = 0.4):
    kc = nc + 1
    g = np.arange(kc) / nc * 2.0 - 1.0
    ab = np.stack([a.ravel() for a in np.meshgrid(g, g, indexing="ij")], 1)
    linf = np.abs(ab).max(axis=1)
    l2 = np.linalg.norm(ab, axis=1)
    disk = ab * np.where(l2 > 1e-12, linf / np.maximum(l2, 1e-12),
                         1.0)[:, None] * r
    u = np.arange(nu)[:, None] / nu * 2.0 * np.pi
    rho = R + disk[None, :, 0]
    vert = np.stack([rho * np.cos(u), rho * np.sin(u),
                     np.broadcast_to(disk[:, 1], (nu, kc * kc))],
                    axis=2).reshape(-1, 3)
    cell = np.stack([a.ravel() for a in np.meshgrid(
        np.arange(nu), np.arange(nc), np.arange(nc), indexing="ij")], 1)
    corners = np.empty((len(cell), 8), np.int64)
    for c in range(8):
        q = cell + np.array([c & 1, (c >> 1) & 1, (c >> 2) & 1])
        corners[:, c] = (q[:, 0] % nu) * (kc * kc) + q[:, 1] * kc + q[:, 2]
    tet = corners[:, load("meshes", "cube")._KUHN_TETS].reshape(-1, 4)
    p = vert[tet]
    flip = np.einsum("ij,ij->i", p[:, 1] - p[:, 0], np.cross(
        p[:, 2] - p[:, 0], p[:, 3] - p[:, 0])) < 0
    tet[flip, 0], tet[flip, 1] = tet[flip, 1], tet[flip, 0].copy()
    return vert, tet.astype(np.int32)
