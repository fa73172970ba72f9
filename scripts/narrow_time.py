"""Steady-state timing of the self-width-selecting auto blocks (bench proxy).

Reproduces bench.py's protocol (N=16 shock cube, capacity factor 8, 9-cycle
fused auto blocks) but times MORE blocks and prints per-block wall ms +
per-cycle narrow/full flags, so the cost of the full-refresh cadence and the
narrow row budget can be measured separately without a 19-minute bench run.

Knobs: NT_N, NT_CAP, NT_BLOCKS, NT_BLOCK (cycles/block), NT_FULL_EVERY
(full-refresh on the last cycle of every k-th block; 0 = never),
PARMMG_NARROW_DIV (ops/active.py row budget).
Run: python scripts/narrow_time.py
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
from parmmg_tpu.ops.active import adapt_cycles_auto
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric


def main():
    n = int(os.environ.get("NT_N", "16"))
    cap = int(os.environ.get("NT_CAP", "8"))
    nblocks = int(os.environ.get("NT_BLOCKS", "6"))
    block = int(os.environ.get("NT_BLOCK", "9"))
    full_every = int(os.environ.get("NT_FULL_EVERY", "1"))

    vert, tet = cube_mesh(n)
    mesh = make_mesh(vert, tet, capP=cap * len(vert), capT=cap * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.5 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)
    from parmmg_tpu.ops.active import narrow_rows
    print(f"N={n} capT={mesh.capT} A={narrow_rows(mesh.capT)} "
          f"block={block} full_every={full_every} "
          f"device={jax.default_backend()}", flush=True)

    def _flags(off):
        return tuple((c + off) % 3 == 2 for c in range(block))

    def _full(bi):
        if full_every == 0:
            return tuple(False for _ in range(block))
        return tuple(c == block - 1 and (bi % full_every == full_every - 1)
                     for c in range(block))

    dirty = jnp.zeros(mesh.capP, bool)
    okflag = jnp.asarray(False)
    m, k = mesh, met
    # warm-up: 2 blocks (second compile for device-layout inputs), plus one
    # of each distinct (swap_flags, full_flags) variant on state copies
    t0 = time.perf_counter()
    m, k, dirty, okflag, c0 = adapt_cycles_auto(
        m, k, dirty, okflag, jnp.asarray(0, jnp.int32),
        swap_flags=_flags(0), full_flags=_full(0))
    jax.block_until_ready(c0)
    print(f"warm block 0: {time.perf_counter()-t0:.1f}s", flush=True)
    t0 = time.perf_counter()
    m, k, dirty, okflag, c0 = adapt_cycles_auto(
        m, k, dirty, okflag, jnp.asarray(block, jnp.int32),
        swap_flags=_flags(block % 3), full_flags=_full(1))
    jax.block_until_ready(c0)
    print(f"warm block 1: {time.perf_counter()-t0:.1f}s", flush=True)
    variants = {(_flags((2 + bi) * block % 3), _full(2 + bi))
                for bi in range(nblocks)}
    variants -= {(_flags(0), _full(0)), (_flags(block % 3), _full(1))}
    for sf, ff in sorted(variants):
        mc = jax.tree.map(jnp.copy, m)
        t0 = time.perf_counter()
        _, _, _, _, cc = adapt_cycles_auto(
            mc, jnp.copy(k), jnp.copy(dirty), okflag,
            jnp.asarray(0, jnp.int32), swap_flags=sf, full_flags=ff)
        jax.block_until_ready(cc)
        print(f"warm variant: {time.perf_counter()-t0:.1f}s", flush=True)

    prev_live = int(np.asarray(c0)[-1][5])
    for bi in range(nblocks):
        off = (2 + bi) * block
        t0 = time.perf_counter()
        m, k, dirty, okflag, counts = adapt_cycles_auto(
            m, k, dirty, okflag, jnp.asarray(off, jnp.int32),
            swap_flags=_flags(off % 3), full_flags=_full(2 + bi))
        cs = np.asarray(counts)
        dt = time.perf_counter() - t0
        entries = [prev_live] + [int(r[5]) for r in cs[:-1]]
        rate = sum(entries) / dt / 1e6
        narrow = "".join("n" if r[7] else "F" for r in cs)
        ops = int(cs[:, 0].sum() + cs[:, 1].sum() + cs[:, 2].sum())
        print(f"block {bi}: {dt*1e3:7.1f} ms  {rate:6.3f} Mtets/s  "
              f"[{narrow}] live={int(cs[-1][5])} topo_ops={ops} "
              f"nact={[int(r[8]) for r in cs]}", flush=True)
        prev_live = int(cs[-1][5])


if __name__ == "__main__":
    main()
