"""Whatever belongs to one mesh, metric, domain or reading is a file of
its own, found by the name a configuration or ``BENCHMARK.json`` gives:
``<kind>/<name>.py`` beside this file.  A later PR adds a file and
edits none.

kind           what the file defines
``meshes``     ``build(**args) -> (vert [n, 3] float64, tet [m, 4] int32)``, positively oriented
``metrics``    ``at(vert, shift=0.0, **args) -> [n] sizes or [n, 6] tensors``
``domains``    ``on_surface(pts [k, 3, 3], domain, tol) -> [k] bool``: does a triangle lie on the surface
``end_to_end``, ``layer_metrics``   ``read(run) -> float | None`` (readers.py says what ``run`` holds)
"""
from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_loaded: dict = {}


def load(kind: str, name: str):
    """The module ``<kind>/<name>.py``."""
    if (kind, name) not in _loaded:
        path = os.path.join(HERE, kind, name + ".py")
        if not NAME.match(name) or not os.path.exists(path):
            raise SystemExit(f"benchmarks: no {kind}/{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"{kind}.{name}".replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[kind, name] = mod
    return _loaded[kind, name]
