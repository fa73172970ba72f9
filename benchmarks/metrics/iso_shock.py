"""Planar shock at x = 0.5 + shift as a size map: h (0.2 + 4 d), d the
distance to the plane.  Returns [n] sizes."""
import numpy as np


def at(vert, h: float, shift: float = 0.0):
    return h * (0.2 + 4.0 * np.abs(vert[:, 0] - (0.5 + shift)))
