"""Attribute adapt-cycle cost by timing flag variants on the live device.

full - light = swap cost; light - nosmooth = smooth cost; nosmooth =
split+collapse+2 adjacency builds.  Each variant is one jit graph; timing
is min of 3 reps from a fresh copy of the same state (adapt_cycle donates
its inputs).  Run: python scripts/cycle_variants.py [N]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from parmmg_tpu.utils.compilecache import set_cache_env  # noqa: E402
set_cache_env()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

import jax
import jax.numpy as jnp
import numpy as np

from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.ops.adapt import adapt_cycle
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    vert, tet = cube_mesh(n)
    mesh = make_mesh(vert, tet, capP=3 * len(vert), capT=3 * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.5 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)
    print(f"N={n}: {len(tet)} tets, capT={mesh.capT}, "
          f"device={jax.devices()[0].platform}")

    # advance one cycle so the timed state has mixed work
    m1, k1, c = adapt_cycle(mesh, met, jnp.asarray(0, jnp.int32))
    jax.block_until_ready(c)

    variants = [
        ("full  (split+col+swap+smooth)", dict()),
        ("light (split+col+smooth)", dict(do_swap=False)),
        ("bare  (split+col)", dict(do_swap=False, do_smooth=False)),
        ("smooth2 (light, 2 waves)", dict(do_swap=False, smooth_waves=2)),
    ]
    for label, kw in variants:
        best = None
        for rep in range(3):
            m = jax.tree.map(jnp.copy, m1)
            k = jnp.copy(k1)
            jax.block_until_ready(k)
            t0 = time.perf_counter()
            m, k, c = adapt_cycle(m, k, jnp.asarray(1, jnp.int32), **kw)
            np.asarray(c)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        print(f"  {label:34s} {best*1e3:9.2f} ms")


if __name__ == "__main__":
    main()
