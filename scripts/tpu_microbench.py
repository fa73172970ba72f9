"""Per-primitive device timing.

A single-op timing is mostly dispatch.  Here each primitive runs K reps
inside ONE jitted fori_loop with a data dependency chained through the
carry, so wall/K approximates the on-device op time with the dispatch
amortized away.

Primitives measured at bench-like shapes (capT=73728, capE=6*capT):
  sort_i32      : argsort of 6*capT int32 keys (the edge-table sort)
  scatter_max   : .at[idx].max into capP pool, duplicate indices (claims)
  scatter_add   : .at[idx].add into capP pool (smooth accumulators)
  gather_rows   : tet row gather [capT,4] -> [capT,4,3] coords
  seg_scan      : associative_scan max over 6*capT (segment heads)
  cross_qual    : quality_from_points on [capT,4,3]
  adjacency     : full build_adjacency on the bench mesh
  edge_table    : full unique_edges on the bench mesh

Run ON TPU (no JAX_PLATFORMS override):  python scripts/tpu_microbench.py
Run on CPU for comparison:               JAX_PLATFORMS=cpu python ...
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

K = int(os.environ.get("MB_REPS", "30"))
N_TET = int(os.environ.get("MB_CAPT", "73728"))
N_P = N_TET // 4
N_E = 6 * N_TET


def timed(name, fn, *args):
    f = jax.jit(fn)
    r = f(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    r = f(*args)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / K
    print(f"{name:14s} {dt * 1e3:9.3f} ms/op   ({K} reps fused)")
    return dt


def loop(body):
    """K-rep fori_loop with carry dependency."""
    def fn(x):
        return jax.lax.fori_loop(0, K, body, x)
    return fn


def main():
    print(f"backend={jax.default_backend()} capT={N_TET} reps={K}")
    key = jax.random.PRNGKey(0)
    keys = jax.random.randint(key, (N_E,), 0, N_P * 197, jnp.int32)
    idx = jax.random.randint(key, (N_E,), 0, N_P, jnp.int32)
    vals = jax.random.uniform(key, (N_E,))
    tets = jax.random.randint(key, (N_TET, 4), 0, N_P, jnp.int32)
    verts = jax.random.uniform(key, (N_P, 3))

    timed("sort_i32", loop(
        lambda i, x: jnp.argsort(x ^ i).astype(jnp.int32)), keys)
    timed("scatter_max", loop(
        lambda i, x: jnp.zeros(N_P, x.dtype).at[idx].max(x) [idx] + x),
        vals)
    timed("scatter_add", loop(
        lambda i, x: jnp.zeros(N_P, x.dtype).at[idx].add(x)[idx] + 0.0 * x),
        vals)
    timed("scatter_uniq", loop(
        lambda i, x: jnp.zeros(N_E, x.dtype).at[
            jnp.arange(N_E)].set(x, unique_indices=True) + 1.0), vals)
    timed("gather_rows", loop(
        lambda i, t: (verts[t].sum((1, 2)) > 0).astype(jnp.int32)[:, None]
        + t), tets)
    timed("seg_scan", loop(
        lambda i, x: jax.lax.associative_scan(jnp.maximum, x ^ i)), keys)

    from parmmg_tpu.ops.quality import quality_from_points

    def qual_body(i, t):
        q = quality_from_points(verts[t])
        return t + (q.sum() > 0).astype(jnp.int32)

    timed("cross_qual", loop(qual_body), tets)

    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.adjacency import build_adjacency
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.ops.edges import unique_edges
    from parmmg_tpu.utils.fixtures import cube_mesh

    vert, tet = cube_mesh(16)
    mesh = make_mesh(vert, tet, capP=N_P, capT=N_TET)
    mesh = analyze_mesh(mesh).mesh

    def adj_body(i, m):
        import dataclasses
        m2 = build_adjacency(m)
        return dataclasses.replace(
            m2, tet=m2.tet + (m2.adja.sum() == -i).astype(jnp.int32))

    timed("adjacency", loop(adj_body), mesh)

    def et_body(i, m):
        import dataclasses
        et = unique_edges(m)
        return dataclasses.replace(
            m, tet=m.tet + (et.nshell.sum() == -i).astype(jnp.int32))

    timed("edge_table", loop(et_body), mesh)


if __name__ == "__main__":
    main()


def payload_scaling():
    """Does scatter cost scale with payload width?  If ~flat, narrow
    scatters should be BATCHED (one wide scatter replaces N narrow)."""
    print(f"\npayload-width scaling (backend={jax.default_backend()})")
    key = jax.random.PRNGKey(1)
    idx = jax.random.randint(key, (N_E,), 0, N_P, jnp.int32)
    for w in (1, 2, 4, 8, 16):
        vals = jax.random.uniform(key, (N_E, w))

        def body(i, x):
            out = jnp.zeros((N_P, w), x.dtype).at[idx].add(x)
            return x + out[idx] * 0.0 + i * 0.0

        timed(f"scat_add_w{w}", loop(body), vals)
    for w in (1, 4, 8):
        vals = jax.random.uniform(key, (N_E, w))

        def body(i, x):
            out = jnp.zeros((N_P, w), x.dtype).at[idx].max(x)
            return x + out[idx] * 0.0 + i * 0.0

        timed(f"scat_max_w{w}", loop(body), vals)
    # gather width scaling
    for w in (1, 8):
        tbl = jax.random.uniform(key, (N_P, w))

        def body(i, x):
            return x + tbl[idx.astype(jnp.int32) + i * 0].sum(-1) * 0.0

        timed(f"gather_w{w}", loop(body),
              jax.random.uniform(key, (N_E,)))
