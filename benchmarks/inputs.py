"""A job's input, made from ``--seed``: the configuration's mesh
(``meshes/<generator>.py``) with its interior vertices moved, and its
metric (``metrics/<kind>.py``) there.  The yardstick's own copies (from
``parmmg_tpu/utils/fixtures.py`` and the seeded jitter of
``chip_smoke.write_input``): a later PR that changes the program's
fixtures cannot change what the benchmark feeds it.  numpy only; nothing
here imports the program.
"""
from __future__ import annotations

import numpy as np

from byname import load
from checker import face_counts


def metric_at(metric_cfg: dict, vert, shift: float = 0.0):
    """The configuration's metric at ``vert``: [n] sizes or [n, 6]."""
    return load("metrics", metric_cfg["kind"]).at(
        np.asarray(vert, np.float64), shift=shift, **metric_cfg["args"])


def boundary_vertices(tet, nvert: int):
    """Mask of the vertices on a face that only one tet has."""
    uniq, cnt = face_counts(tet)
    on = np.zeros(nvert, bool)
    on[uniq[cnt == 1].ravel()] = True
    return on


def build_input(config: dict, seed: int) -> dict:
    """Mesh and metric of one job.  The seed moves every interior vertex
    by up to ``jitter`` (a twentieth of a cell) along each axis: the same
    topology and the same amount of work, a different input to every
    geometric predicate."""
    mesh = config["mesh"]
    vert, tet = load("meshes", mesh["generator"]).build(**mesh["args"])
    rng = np.random.default_rng(seed)
    inner = ~boundary_vertices(tet, len(vert))
    vert = vert.copy()
    vert[inner] += rng.uniform(-mesh["jitter"], mesh["jitter"],
                               (int(inner.sum()), 3))
    return {"vert": vert, "tet": tet, "met": metric_at(config["metric"], vert)}
