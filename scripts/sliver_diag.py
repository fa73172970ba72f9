"""Diagnose the surviving worst tets after adaptation + polish (CPU).

Prints, for the N worst tets: quality, how many vertices/faces/edges are
boundary/required, and which polish op could in principle apply — to see
why sliver_polish leaves them behind.
Run: python scripts/sliver_diag.py [N] [cycles]
"""
from __future__ import annotations

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import jax.numpy as jnp
import numpy as np

from parmmg_tpu.core import constants as C
from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.ops.adapt import adapt_cycles_fused, sliver_polish
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.quality import quality_from_points
from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 12
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
        m, k, _ = adapt_cycles_fused(m, k, jnp.asarray(b, jnp.int32),
                                     n_cycles=nc, swap_every=3)
    for w in range(4):
        m, pc = sliver_polish(m, k, jnp.asarray(100 + w, jnp.int32))
        pcs = np.asarray(pc)
        print(f"polish {w}: collapse {pcs[0]} swap {pcs[1]} move {pcs[2]}")
        if pcs[0] == 0 and pcs[1] == 0:
            break

    q = np.asarray(quality_from_points(m.vert[m.tet]))
    tm = np.asarray(m.tmask)
    q = np.where(tm, q, np.inf)
    worst = np.argsort(q)[:12]
    tv = np.asarray(m.tet)
    vtag = np.asarray(m.vtag)
    ftag = np.asarray(m.ftag)
    etag = np.asarray(m.etag)
    vh = np.asarray(m.vert)
    for t in worst:
        vids = tv[t]
        nb = sum(1 for v in vids if vtag[v] & C.MG_BDY)
        nreq = sum(1 for v in vids if vtag[v] & C.MG_REQ)
        nbf = sum(1 for f in range(4) if ftag[t, f] & C.MG_BDY)
        nte = sum(1 for e in range(6) if etag[t, e] & (C.MG_BDY | C.MG_GEO
                                                       | C.MG_REQ))
        print(f"tet {t}: q={q[t]:.6f} bdyV={nb}/4 reqV={nreq} "
              f"bdyF={nbf} taggedE={nte} verts={[tuple(np.round(vh[v],3)) for v in vids]}")


if __name__ == "__main__":
    main()
