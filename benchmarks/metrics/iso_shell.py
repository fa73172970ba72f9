"""Surface shell of the unit ball as a size map: h (0.2 + 4 d), d the
distance to the sphere of radius 1 - shift: ``iso_shock``'s plane bent
onto the curved boundary, finest ON it.  Returns [n] sizes."""
import numpy as np


def at(vert, h: float, shift: float = 0.0):
    return h * (0.2 + 4.0 * np.abs(
        1.0 - shift - np.linalg.norm(vert, axis=1)))
