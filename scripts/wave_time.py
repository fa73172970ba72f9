"""Per-wave phase timing on the TPU (or CPU).

The cycle-level numbers said ~600 ms/cycle at bench shapes
but the known primitives (adjacency 42 ms, edge table 14 ms, scatters
~9 ms) sum to a fraction of that — this script closes the attribution gap
by timing each WAVE KERNEL separately, K reps fused in one jitted
fori_loop with the mesh chained through the carry (same dispatch-
amortization trick as tpu_microbench.py).

Because every wave is shape-static, its cost is a function of the
capacities, not of how many ops actually apply — chaining reps is
representative even when later reps find nothing to do.

Run: python scripts/wave_time.py [N] (default 16 = bench shape)
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
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.adjacency import build_adjacency, boundary_edge_tags
from parmmg_tpu.ops.split import split_wave
from parmmg_tpu.ops.collapse import collapse_wave
from parmmg_tpu.ops.swap import swap_edges_wave, swap23_wave
from parmmg_tpu.ops.smooth import smooth_wave
from parmmg_tpu.ops.edges import unique_edges, edge_lengths
from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric

K = int(os.environ.get("WT_REPS", "10"))


def timed(name, body, mesh, met):
    def loop(mesh, met):
        def it(_, mk):
            m, k = mk
            return body(m, k)
        return jax.lax.fori_loop(0, K, it, (mesh, met))

    f = jax.jit(loop, donate_argnums=())
    r = f(mesh, met)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    r = f(mesh, met)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / K
    print(f"{name:22s} {dt * 1e3:9.2f} ms/wave   ({K} reps fused)")
    return dt


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    vert, tet = cube_mesh(n)
    mesh = make_mesh(vert, tet, capP=3 * len(vert), capT=3 * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.5 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)
    print(f"N={n} capP={mesh.capP} capT={mesh.capT} "
          f"device={jax.default_backend()}")

    total = {}
    # edge table / lengths return no Mesh: chain a zero-valued data
    # dependency through the metric so the loop carry stays (Mesh, met)
    total["edge_table"] = timed(
        "edge_table", lambda m, k: (
            m, k + 0.0 * unique_edges(m).nshell[0]), mesh, met)
    total["edge_tab+len"] = timed(
        "edge_table+lengths", lambda m, k: (
            m, k + 0.0 * edge_lengths(m, unique_edges(m), k)[0]),
        mesh, met)
    total["adjacency"] = timed(
        "adjacency", lambda m, k: (build_adjacency(m), k), mesh, met)
    total["bdy_edge_tags"] = timed(
        "boundary_edge_tags", lambda m, k: (boundary_edge_tags(m), k),
        mesh, met)
    total["split"] = timed(
        "split_wave", lambda m, k: (lambda r: (r.mesh, r.met))(
            split_wave(m, k)), mesh, met)
    total["collapse"] = timed(
        "collapse_wave", lambda m, k: (collapse_wave(m, k).mesh, k),
        mesh, met)
    total["swap_edges"] = timed(
        "swap_edges(3-2,2-2)", lambda m, k: (swap_edges_wave(m, k).mesh, k),
        mesh, met)
    total["swap23"] = timed(
        "swap23(needs adja)", lambda m, k: (
            swap23_wave(build_adjacency(m), k).mesh, k), mesh, met)
    total["smooth"] = timed(
        "smooth_wave", lambda m, k: (
            smooth_wave(m, k, wave=jnp.asarray(0, jnp.int32)).mesh, k),
        mesh, met)

    # reference composition: one light cycle = split + bdy_tags + collapse
    # + 2x smooth; one full cycle adds swaps + adjacency
    light = (total["split"] + total["collapse"] + total["bdy_edge_tags"]
             + 2 * total["smooth"])
    full = light + total["swap_edges"] + total["swap23"]
    print(f"\ncomposed light cycle ~ {light * 1e3:.1f} ms, "
          f"full cycle ~ {full * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
