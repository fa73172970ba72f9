"""Deterministic CPU quality check of a shock-metric cube (small N).

Runs whole-mesh adapt cycles (swap every third) under the planar shock
size map on the CPU backend and prints final qmin/qmean/ntets — used to
compare wave-scheduling changes (claim orders, swap cadence) for quality impact.
Run: python scripts/quality_check.py [N] [cycles]
"""
from __future__ import annotations

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import jax.numpy as jnp
import numpy as np

from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.ops.adapt import adapt_cycle
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.quality import tet_quality
from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    cycles = int(sys.argv[2]) if len(sys.argv) > 2 else 9
    vert, tet = cube_mesh(n)
    mesh = make_mesh(vert, tet, capP=3 * len(vert), capT=3 * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.5 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)

    m, k = mesh, met
    for c in range(cycles):
        m, k, counts = adapt_cycle(m, k, jnp.asarray(c, jnp.int32),
                                   do_swap=c % 3 == 2)
        r = np.asarray(counts)
        print(f"  cycle: split {r[0]:6d} collapse {r[1]:6d} "
              f"swap {r[2]:6d} move {r[3]:6d} live {r[5]:6d}")
    q = np.asarray(tet_quality(m, k))
    tm = np.asarray(m.tmask)
    qs = np.sort(q[tm])
    print(f"N={n} cycles={cycles}: ntets={tm.sum()} "
          f"qmin={qs[0]:.6f} q1%={qs[len(qs)//100]:.4f} "
          f"qmean={qs.mean():.4f}")


if __name__ == "__main__":
    main()
