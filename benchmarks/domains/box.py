"""An axis-aligned box ``lo``..``hi``: a triangle lies on its surface
when its three vertices share one of the six planes."""
import numpy as np


def on_surface(pts, domain: dict, tol: float):
    on = np.zeros(len(pts), bool)
    for ax in range(3):
        for val in (domain["lo"][ax], domain["hi"][ax]):
            on |= np.all(np.abs(pts[:, :, ax] - val) < tol, axis=1)
    return on
