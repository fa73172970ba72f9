"""Phase-level wall-clock profile of one adapt cycle on the live device.

Times each sub-operator (edge table, lengths, split, adjacency, collapse,
swaps, smooth) with block_until_ready, after a compile warm-up, to show
where an adapt cycle's time goes.  Run: python scripts/profile_adapt.py [N]

**Device-timeline capture** (ROADMAP item 1d / 4 prerequisite — the
one-pass profile recipe, TPU-ready, runnable today on the CPU backend):

    PARMMG_PROFILE_DIR=/tmp/prof python scripts/profile_adapt.py 16

arms ``jax.profiler.start_trace`` over the timed section via the obs
capture-window machinery (obs/trace.py) — every ``timeit`` label lands
on the profiler timeline as a ``TraceAnnotation``, and the grouped
paths' ``named_scope`` phase names annotate the XLA ops, so the
TensorBoard/xprof view carries the SAME phase vocabulary as the host
trace JSONL.  The same env knob arms a capture around outer pass
``PARMMG_PROFILE_PASS=start[:stop]`` of any grouped/distributed run
(driver, bench, scale_big workers) — this script is just the smallest
recipe that produces a timeline.

``--json PATH`` additionally writes the captured phase->milliseconds
map to PATH; ``bench.py`` embeds it into the artifact under
``extra.profile_phases`` when ``BENCH_PROFILE_JSON`` points at it, so
a checked-in BENCH round carries the one-pass phase profile and the
next chip session can diff the SAME phase names on a real timeline.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from parmmg_tpu.utils.compilecache import set_cache_env  # noqa: E402

set_cache_env()

import jax
import jax.numpy as jnp
import numpy as np

from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.ops import adjacency as adj
from parmmg_tpu.ops.adapt import adapt_cycle
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.collapse import collapse_wave
from parmmg_tpu.ops.edges import unique_edges, edge_lengths, unique_priority
from parmmg_tpu.ops.smooth import smooth_wave
from parmmg_tpu.ops.split import split_wave
from parmmg_tpu.ops.swap import swap23_wave, swap32_wave
from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric


PHASES_MS: dict[str, float] = {}    # label -> min ms (the --json payload)
INCR: dict = {}                     # incremental-topology occupancy facts


def timeit(label, fn, *args, reps=3, **kw):
    jfn = jax.jit(fn, **kw)
    out = jfn(*args)
    jax.block_until_ready(out)          # compile + warm
    ts = []
    for _ in range(reps):
        # annotate: the label shows on the profiler's device timeline
        # when a capture is armed (free nullcontext otherwise)
        with otrace.annotate(label):
            t0 = time.perf_counter()
            out = jfn(*args)
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
    PHASES_MS[label] = round(min(ts) * 1e3, 3)
    print(f"  {label:28s} {min(ts)*1e3:9.2f} ms")
    return out


def main():
    argv = sys.argv[1:]
    json_out = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            sys.exit("usage: profile_adapt.py [n] [--json PATH]")
        json_out = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    n = int(argv[0]) if argv else 16
    vert, tet = cube_mesh(n)
    mesh = make_mesh(vert, tet, capP=3 * len(vert), capT=3 * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.5 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)
    print(f"N={n}: {len(tet)} tets, capT={mesh.capT}, capP={mesh.capP}, "
          f"device={jax.devices()[0].platform}")

    # capture window: with PARMMG_PROFILE_DIR set this arms the
    # profiler over the timed section below (treated as "pass 0" — the
    # default PARMMG_PROFILE_PASS window); warm-up compiles above this
    # line stay OUT of the capture so the timeline shows steady state
    otrace.profile_pass_begin(0)

    # NOTE: every prep value is produced by a jitted call — eager array
    # code pays a dispatch PER OP
    et = timeit("unique_edges", unique_edges, mesh)
    lens = timeit("edge_lengths", edge_lengths, mesh, et, met)
    timeit("unique_priority", unique_priority, lens, et.emask)
    # sort/segment sub-phases under STABLE names —
    # BENCH rounds diff exactly these sort/segment legs on CPU and chip.
    # unique_edges_sort/segment split unique_edges' packed sort from its
    # unique-head selection; priority_sort is unique_priority's argsort
    # leg; face_sort the packed face lexsort (same pass swap_face_pairs
    # times below, under its own stable name); band_sort the
    # incremental band's local sort.
    from parmmg_tpu.core.mesh import tet_edge_vertices
    from parmmg_tpu.ops.edges import (sort_pairs, priority_order,
                                      segment_first)

    def _edge_cols(m):
        ev = tet_edge_vertices(m.tet).reshape(m.capT * 6, 2)
        return (jnp.minimum(ev[:, 0], ev[:, 1]),
                jnp.maximum(ev[:, 0], ev[:, 1]),
                jnp.repeat(m.tmask, 6))
    a6, b6, v6 = jax.jit(_edge_cols)(mesh)
    capP = mesh.capP
    timeit("unique_edges_sort",
           lambda a, b, v: sort_pairs(a, b, v, capP)[0], a6, b6, v6)
    ks6 = jax.jit(lambda a, b, v: jnp.sort(jnp.where(
        v, a * capP + b, jnp.iinfo(jnp.int32).max)))(a6, b6, v6)
    timeit("unique_edges_segment",
           lambda k: segment_first((k,)), ks6)
    neg = jax.jit(lambda le, em: jnp.where(em, -le, jnp.inf))(
        lens, et.emask)
    timeit("priority_sort", priority_order, neg)
    timeit("face_sort", adj.face_sort, mesh)
    timeit("split_wave", lambda m, k: split_wave(m, k), mesh, met)
    timeit("build_adjacency", adj.build_adjacency, mesh)
    timeit("collapse_wave", lambda m, k: collapse_wave(m, k), mesh, met)
    timeit("boundary_edge_tags", adj.boundary_edge_tags, mesh)
    timeit("swap32_wave", lambda m, k: swap32_wave(m, k), mesh, met)
    timeit("swap23_wave", lambda m, k: swap23_wave(m, k), mesh, met)
    # hot-loop attack segments (README "Cycle-cost demolition"): STABLE
    # phase names — BENCH rounds diff these across sessions, keep them.
    # swap_face_pairs: the face-sort records swap23 pairs off when
    # PARMMG_SWAP_FACESORT is on (vs build_adjacency + swap23_wave)
    timeit("swap_face_pairs", adj.face_sort, mesh)
    timeit("swap23_facesort",
           lambda m, k: swap23_wave(m, k, facesort=True), mesh, met)
    # collapse_wave_fullwidth: the PARMMG_COLLAPSE_BAND=0 arm — the
    # donor-band saving is (collapse_wave_fullwidth - collapse_wave)
    os.environ["PARMMG_COLLAPSE_BAND"] = "0"
    try:
        timeit("collapse_wave_fullwidth",
               lambda m, k: collapse_wave(m, k), mesh, met)
    finally:
        del os.environ["PARMMG_COLLAPSE_BAND"]
    timeit("smooth_wave", lambda m, k: smooth_wave(m, k), mesh, met)

    # incremental-topology segments (PARMMG_INCR_TOPO, ops/topo_incr):
    # STABLE phase names — band_extract / band_merge / band_adjacency
    # vs the full-rebuild names above (unique_edges, build_adjacency,
    # boundary_edge_tags).  Timed at a half-full band, the decay-regime
    # shape the knob targets.
    from parmmg_tpu.ops.adapt import adapt_cycle_impl
    from parmmg_tpu.ops.topo_incr import (
        edge_band_records, incr_band_width, incr_build_adjacency,
        incr_unique_edges, topo_init)
    bw = incr_band_width(mesh.capT)
    on = jnp.ones((), bool)

    def _seed(m, t):
        _, t = incr_unique_edges(m, t, on)
        _, t = incr_build_adjacency(m, t, on)
        return t
    topo1 = jax.jit(_seed)(mesh, topo_init(mesh.capT))
    live = np.flatnonzero(np.asarray(mesh.tmask))[:max(1, bw // 2)]
    dirty = np.zeros(mesh.capT, bool)
    dirty[live] = True
    topo_d = topo1._replace(edirty=jnp.asarray(dirty),
                            fdirty=jnp.asarray(dirty))
    dt = jnp.asarray(np.concatenate(
        [live, np.full(bw - len(live), mesh.capT)]).astype(np.int32))
    from parmmg_tpu.ops.topo_incr import band_order
    bkey6, bslot6 = timeit("band_extract", edge_band_records, mesh, dt)
    timeit("band_sort",
           lambda bk, bs: band_order((bk,), bs), bkey6, bslot6)
    timeit("band_merge",
           lambda m, t: incr_unique_edges(m, t, on), mesh, topo_d)
    timeit("band_adjacency",
           lambda m, t: incr_build_adjacency(m, t, on), mesh, topo_d)
    # per-cycle dirty-band occupancy: thread TopoState through real
    # cycles and read counts[8] (dirty tets at cycle start) — the
    # occupancy the band (width bw) must absorb to stay incremental
    step = jax.jit(lambda m, k, w, t: adapt_cycle_impl(
        m, k, w, topo=t, incr=on))
    mi, ki, ti = mesh, met, topo1
    occ = []
    for cyc in range(4):
        mi, ki, cnt, ti = step(mi, ki, jnp.asarray(cyc, jnp.int32), ti)
        occ.append(int(np.asarray(cnt)[8]))
    INCR.update(band_width=bw, band_dirty=int(dirty.sum()),
                dirty_per_cycle=occ)
    print(f"  {'dirty band':28s} width {bw}, per-cycle occupancy {occ}")

    # full cycles, as bench runs them.  adapt_cycle DONATES its inputs, so
    # deep-copy the state before each flavor (and time the second call —
    # the first may absorb a compile or a transport stall)
    m1, k1, c = adapt_cycle(mesh, met, jnp.asarray(0, jnp.int32))
    jax.block_until_ready(c)
    for do_swap in (True, False):
        for rep in range(2):
            m = jax.tree.map(jnp.copy, m1)
            k = jnp.copy(k1)
            jax.block_until_ready(k)
            with otrace.annotate(f"adapt_cycle_swap{int(do_swap)}"):
                t0 = time.perf_counter()
                m, k, c = adapt_cycle(m, k, jnp.asarray(1, jnp.int32),
                                      do_swap=do_swap)
                np.asarray(c)
                dt = time.perf_counter() - t0
        print(f"  adapt_cycle(do_swap={do_swap!s:5}) "
              f"{dt*1e3:9.2f} ms  counts={np.asarray(c)[:5]}")
        PHASES_MS[f"adapt_cycle_swap{int(do_swap)}"] = round(dt * 1e3, 3)

    otrace.profile_pass_end(0)

    if json_out:
        with open(json_out, "w") as f:
            json.dump({"n": n, "ntets": len(tet),
                       "device": jax.devices()[0].platform,
                       "phases_ms": PHASES_MS, "incr": INCR}, f,
                      indent=1)
        print(f"profile: phase timings written to {json_out}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
