"""A ball ``centre``, ``radius``: a triangle lies on its surface when its
three vertices are within ``vertex_tol`` of the sphere AND its centroid
(the deepest point of the flat chord) within ``chord_tol``; both are the
configuration's, so the caller's ``tol`` (a box's 1e-9) is not used."""
import numpy as np


def deviations(pts, domain: dict):
    """(vertex [k, 3], chord [k]) distances to the sphere, >= 0."""
    rel = np.asarray(pts, np.float64) - np.asarray(domain["centre"])
    r = domain["radius"]
    return (np.abs(np.linalg.norm(rel, axis=2) - r),
            np.abs(np.linalg.norm(rel.mean(axis=1), axis=1) - r))


def on_surface(pts, domain: dict, tol: float):
    vertex, chord = deviations(pts, domain)
    return (vertex.max(axis=1, initial=0.0) <= domain["vertex_tol"]) & \
        (chord <= domain["chord_tol"])
