"""Deterministic CPU quality check of the bench workload (small N).

Runs the same shock-metric cube adaptation as bench.py at a reduced size
on the CPU backend and prints final qmin/qmean/ntets — used to compare
wave-scheduling changes (claim orders, swap cadence) for quality impact.
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
from parmmg_tpu.ops.adapt import adapt_cycles_fused
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
    for b in range(0, cycles, 3):
        nc = min(3, cycles - b)
        m, k, counts = adapt_cycles_fused(m, k, jnp.asarray(b, jnp.int32),
                                          n_cycles=nc, swap_every=3)
        cs = np.asarray(counts)
        for r in cs:
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
