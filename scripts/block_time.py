"""Steady-state fused-block timing (bench proxy, ~2 min vs 9 min bench).
Times the SECOND and THIRD 3-cycle fused block after warm-up.
Run: python scripts/block_time.py [N]"""
from __future__ import annotations
import os, sys, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from parmmg_tpu.utils.compilecache import set_cache_env  # noqa: E402
set_cache_env()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
import jax, jax.numpy as jnp, numpy as np
from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.ops.adapt import adapt_cycles_fused
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric

def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    vert, tet = cube_mesh(n)
    mesh = make_mesh(vert, tet, capP=3 * len(vert), capT=3 * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.5 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[:len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)
    print(f"N={n} capT={mesh.capT} device={jax.default_backend()}")
    m, k = mesh, met
    times = []
    for b in range(5):
        t0 = time.perf_counter()
        m, k, counts = adapt_cycles_fused(m, k, jnp.asarray(3 * b, jnp.int32),
                                          n_cycles=3, swap_every=3)
        c = np.asarray(counts)
        dt = time.perf_counter() - t0
        times.append(dt)
        print(f"block {b}: {dt*1e3:8.1f} ms  live={c[-1][5]}")
    print(f"steady median: {np.median(times[1:])*1e3:.1f} ms/block")

if __name__ == "__main__":
    main()
