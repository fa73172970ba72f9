"""Does batching (vmap over groups) reduce scatter/gather/scan cost?

Measures the bench-critical primitives flat at [n] vs vmapped at
[G, n/G]: if TPU scatter cost is per-index (linear), the grouped form
changes nothing; if there is a big per-op serial component that batch
dims vectorize away, the S*G logical-shard composition is THE
throughput lever.  Also re-checks the suspicious 4us sort number at
several widths with a sum-dependency (argsort result fed through a
gather so DCE cannot drop the comparator work).

Run: python scripts/scatter_scaling.py
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

K = int(os.environ.get("SS_REPS", "30"))
N = int(os.environ.get("SS_N", str(6 * 73728)))     # bench capE
NP_ = N // 6                                         # pool size


def timed(name, fn, *args):
    f = jax.jit(fn)
    r = f(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    r = f(*args)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / K * 1e3
    print(f"{name:34s} {dt:9.3f} ms/op")
    return dt


def loop(body):
    def fn(x):
        return jax.lax.fori_loop(0, K, body, x)
    return fn


def main():
    print(f"backend={jax.default_backend()} N={N} reps={K}")
    key = jax.random.PRNGKey(0)
    idx = jax.random.randint(key, (N,), 0, NP_, jnp.int32)
    vals = jax.random.uniform(key, (N,))

    # flat scatter-max (dup indices), the claim primitive
    timed("scatter_max flat", loop(
        lambda i, x: jnp.zeros(NP_, x.dtype).at[idx].max(x)[idx] + x), vals)

    for G in (8, 32):
        n_g = N // G
        np_g = NP_ // G
        idx_g = (idx[: G * n_g].reshape(G, n_g) % np_g).astype(jnp.int32)
        vals_g = vals[: G * n_g].reshape(G, n_g)

        def body_g(i, x, idx_g=idx_g, np_g=np_g):
            out = jax.vmap(
                lambda ix, xv: jnp.zeros(np_g, xv.dtype).at[ix].max(xv))(
                idx_g, x)
            return jnp.take_along_axis(out, idx_g, 1) + x
        timed(f"scatter_max vmap G={G}", loop(body_g), vals_g)

    # gather
    timed("gather flat", loop(
        lambda i, x: x[idx] + 0.5), vals)
    for G in (8,):
        n_g = N // G
        idx_g = (idx[: G * n_g].reshape(G, n_g) % n_g).astype(jnp.int32)
        vals_g = vals[: G * n_g].reshape(G, n_g)
        timed(f"gather vmap G={G}", loop(
            lambda i, x, ig=idx_g: jnp.take_along_axis(x, ig, 1) + 0.5),
            vals_g)

    # associative scan
    timed("assoc_scan flat", loop(
        lambda i, x: jax.lax.associative_scan(jnp.maximum, x) * 0.999),
        vals)
    timed("assoc_scan vmap G=8", loop(
        lambda i, x: jax.lax.associative_scan(
            jnp.maximum, x, axis=1) * 0.999),
        vals.reshape(8, N // 8))
    # cumsum (used for offsets)
    timed("cumsum flat", loop(
        lambda i, x: jnp.cumsum(x) * 0.999), vals)

    # sort with un-DCE-able dependency: gather by the returned permutation
    for n in (N, N // 8):
        v = vals[:n]
        timed(f"argsort+gather n={n}", loop(
            lambda i, x: x[jnp.argsort(x)][::-1]), v)
    timed("argsort+gather vmap 8x", loop(
        lambda i, x: jnp.take_along_axis(x, jnp.argsort(x, axis=1), 1)
        [:, ::-1]), vals.reshape(8, N // 8))

    # top_k at bench budget
    timed("top_k K=N/48 flat", loop(
        lambda i, x: x.at[jax.lax.top_k(x, N // 48)[1]].add(1e-7)), vals)


if __name__ == "__main__":
    main()
