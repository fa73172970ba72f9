"""A solid torus round the z axis through ``centre``: ring radius ``R``,
tube radius ``r``.  A point's distance to its surface is
``abs(sqrt((sqrt(x^2 + y^2) - R)^2 + z^2) - r)``.  A triangle lies on the
surface when its three vertices are within ``vertex_tol`` of it AND its
centroid (the deepest point of a flat chord over a convex patch; on the
inner half, where the ring bends the other way, the chord stands partly
OUTSIDE the solid and the distance counts the same) within
``chord_tol``; both are the configuration's, so the caller's ``tol`` (a
box's 1e-9) is not used."""
import numpy as np


def distance(pts, domain: dict):
    """Distance [...] >= 0 of points [..., 3] to the torus's surface."""
    rel = np.asarray(pts, np.float64) - np.asarray(
        domain.get("centre", (0.0, 0.0, 0.0)))
    ring = np.hypot(rel[..., 0], rel[..., 1]) - domain["R"]
    return np.abs(np.hypot(ring, rel[..., 2]) - domain["r"])


def deviations(pts, domain: dict):
    """(vertex [k, 3], chord [k]) distances to the torus of triangles
    ``pts`` [k, 3, 3]: of each corner, and of the centroid."""
    pts = np.asarray(pts, np.float64).reshape(-1, 3, 3)
    return distance(pts, domain), distance(pts.mean(axis=1), domain)


def on_surface(pts, domain: dict, tol: float):
    vertex, chord = deviations(pts, domain)
    return (vertex.max(axis=1, initial=0.0) <= domain["vertex_tol"]) & \
        (chord <= domain["chord_tol"])
