"""The planar shock as a tensor: the iso shock's size ACROSS the plane,
``h_tan`` along it.  Returns [n, 6] (m11, m12, m13, m22, m23, m33)."""
import numpy as np

from byname import load


def at(vert, h: float, h_tan: float = 0.45, shift: float = 0.0):
    m = np.zeros((len(vert), 6))
    m[:, 0] = 1.0 / load("metrics", "iso_shock").at(vert, h, shift) ** 2
    m[:, 3] = m[:, 5] = 1.0 / h_tan ** 2
    return m
