#!/usr/bin/env python3
"""Chip smoke: the grouped adapt path end to end on one TPU, one process.

    python chip_smoke.py                 # one chip (what the driver runs)
    python chip_smoke.py --four-chips    # SPMD path only, four chips

Default phase: a seeded shock cube (``utils/fixtures.cube_mesh(--n)`` +
``analytic_iso_metric(..., "shock")``) is written as Medit files and
adapted through ``parmmg_tpu.cli.main`` with ``-mesh-size 16384
-niter 2`` — the grouped path of ``driver.parmmg_run``.  The run then
checks, by means independent of the code under test where it can: CLI
return 0, every ``resilience.*`` counter 0, the output mesh re-read from
disk conforming with quality above the floor the CPU tests assert, each
surviving Pallas kernel equal to its jnp reference on a small input and
present as ``tpu_custom_call`` in the lowered group block, and no
``groups.*`` compile entry over its budget.

``--four-chips`` runs ONLY the ``-ndev 4`` SPMD path on the same input
and compares it with the one-device result (read from the file a
previous one-chip phase wrote, else recomputed on device 0).

Exits non-zero, printing no result line, when jax finds no TPU.  The
last stdout line of a good run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
N_CUBE = 24                # cube_mesh(24): 82,944 tets, 6 groups
H_SHOCK = 3.2              # shock metric: sizes H_SHOCK/N_CUBE * (0.2 + 4d)
MESH_SIZE = 16384          # upstream's group target (-mesh-size)
NITER = 2                  # interface displacement + regrouping run
QMIN_FLOOR = 1e-3          # tests/test_groups_shards.py: cube + shock, grouped
# global tet-count tolerance of the four-chip result against the
# one-device result: what tests/test_band_path.py:183 allows between two
# paths on one input, |a - b| <= 0.3 max(a, b)
NTET_RTOL = 0.3
# vol / (sum l^2)^1.5 of the regular tetrahedron: (a^3 / (6 sqrt 2)) /
# (6 a^2)^1.5
Q_REGULAR = 1.0 / (6.0 * 2.0 ** 0.5 * 6.0 ** 1.5)
# worst relative error a float kernel may show against float64 on the
# chip (Mosaic's f32 divide and log are good to about 4e-4 there; the
# CPU tests pin 2e-5 against the jnp formula in interpret mode)
KERNEL_RTOL = 1e-3
# Pallas kernels the iso grouped block dispatches on tpu (the quality
# kernels sit in ops/quality.tet_quality, outside the block; the prefix
# sum's only caller is the host tail's merge, ops/topo_incr)
KERNELS = ("edge_length_iso", "score_count", "score3_count")


def say(*a):
    print(*a, flush=True)


def fail(msg: str) -> "NoReturn":
    say(f"chip_smoke: FAIL — {msg}")
    sys.exit(1)


# ---------------------------------------------------------------------------
# input
# ---------------------------------------------------------------------------
def write_input(out_dir: str, seed: int, n: int = N_CUBE,
                h: float = H_SHOCK) -> tuple[str, str, int, int]:
    """Seeded shock cube as Medit .mesh/.sol; returns paths + sizes."""
    from parmmg_tpu.io.medit import MeditMesh, SOL_SCALAR, write_mesh, \
        write_sol
    from parmmg_tpu.utils.fixtures import analytic_iso_metric, cube_mesh
    vert, tet = cube_mesh(n)
    # the seed jitters interior vertices by a twentieth of a cell: the
    # same topology, a different input to every geometric predicate
    rng = np.random.default_rng(seed)
    inner = np.all((vert > 1e-9) & (vert < 1 - 1e-9), axis=1)
    vert = vert.copy()
    vert[inner] += rng.uniform(-0.05 / n, 0.05 / n, (int(inner.sum()), 3))
    size = analytic_iso_metric(vert, "shock", h=h / n)
    m = MeditMesh()
    m.vert = vert.astype(np.float64)
    m.vref = np.zeros(len(vert), np.int32)
    m.tetra = tet.astype(np.int32)
    m.tref = np.ones(len(tet), np.int32)
    mesh_p = os.path.join(out_dir, "cube.mesh")
    sol_p = os.path.join(out_dir, "cube.sol")
    write_mesh(mesh_p, m)
    write_sol(sol_p, size.reshape(-1, 1), [SOL_SCALAR])
    return mesh_p, sol_p, len(vert), len(tet)


# ---------------------------------------------------------------------------
# the plain reference: conformity and quality of a mesh file, numpy only
# ---------------------------------------------------------------------------
def check_output_mesh(path: str) -> dict:
    from parmmg_tpu.io.medit import read_mesh
    m = read_mesh(path)
    vert, tet = np.asarray(m.vert), np.asarray(m.tetra)
    if len(tet) == 0:
        fail("output mesh has no tetrahedra")
    if tet.min() < 0 or tet.max() >= len(vert):
        fail("output mesh references missing vertices")
    if not np.isfinite(vert).all():
        fail("output mesh has non-finite coordinates")
    p = vert[tet]
    d1, d2, d3 = p[:, 1] - p[:, 0], p[:, 2] - p[:, 0], p[:, 3] - p[:, 0]
    vol = np.einsum("ij,ij->i", d1, np.cross(d2, d3)) / 6.0
    if not (vol > 0).all():
        fail(f"{int((vol <= 0).sum())} inverted or flat tets in the output")
    if not np.isclose(vol.sum(), 1.0, rtol=1e-4):
        fail(f"output volume {vol.sum()!r} != 1 (unit cube)")
    # manifold conformity: interior faces matched exactly twice, the
    # unmatched ones tile the cube's surface (area 6)
    faces = np.sort(np.stack([tet[:, [1, 2, 3]], tet[:, [0, 2, 3]],
                              tet[:, [0, 1, 3]], tet[:, [0, 1, 2]]],
                             axis=1).reshape(-1, 3), axis=1)
    key = (faces[:, 0].astype(np.int64) << 42) | \
        (faces[:, 1].astype(np.int64) << 21) | faces[:, 2].astype(np.int64)
    _, idx, cnt = np.unique(key, return_index=True, return_counts=True)
    if cnt.max() > 2:
        fail("non-manifold face in the output")
    # unmatched faces off the cube's surface border a void; the volume
    # sum above bounds its size, so what is left is the flat kind (a
    # missing sliver), which the repo's own oracle does not see
    bf = vert[faces[idx[cnt == 1]]]
    on_surface = np.zeros(len(bf), bool)
    for ax in range(3):
        for val in (0.0, 1.0):
            on_surface |= np.all(np.abs(bf[:, :, ax] - val) < 1e-9, axis=1)
    if not on_surface.all():
        fail(f"{int((~on_surface).sum())} unmatched interior faces: the "
             "output mesh has a hole")
    # Mmg's isotropic quality vol / (sum of squared edges)^1.5, scaled
    # to 1 on the regular tetrahedron
    l2 = sum(((p[:, j] - p[:, i]) ** 2).sum(axis=1)
             for i, j in ((0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)))
    q = vol / l2 ** 1.5 / Q_REGULAR
    return {"nvert": int(len(vert)), "ntets": int(len(tet)),
            "qmin": float(q.min()), "qmean": float(q.mean())}


# ---------------------------------------------------------------------------
# kernels: each against its jnp reference on a small input, on the chip
# ---------------------------------------------------------------------------
def check_kernels(seed: int) -> dict:
    """References are the same formulas in numpy float64 on the host —
    independent of jax (whose f32 einsum runs at reduced precision on a
    TPU) and of the kernels.  Returns each kernel's worst relative
    error (0 for the bit-exact ones)."""
    import jax
    import jax.numpy as jnp
    from parmmg_tpu.core.constants import ALPHA_TET
    from parmmg_tpu.ops import pallas_kernels as pk
    rng = np.random.default_rng(seed)
    n = 5003                                    # odd: pads the last block
    f32 = lambda a: np.asarray(a, np.float32)
    p0, p1 = f32(rng.random((n, 3))), f32(rng.random((n, 3)))
    # sizes differ by a factor >= 1.3 or not at all: the log-mean is
    # ill-conditioned in between, where f32 itself loses the digits
    h0 = f32(0.1 + rng.random(n))
    h1 = f32(h0 * (1.3 + rng.random(n)) ** rng.choice([-1.0, 1.0], n))
    h1[::7] = h0[::7]                           # the equal-size branch
    a = rng.standard_normal((2, n, 3, 3))
    spd = f32(np.einsum("knij,knlj->knil", a, a) + 0.5 * np.eye(3))
    m6 = np.stack([spd[..., 0, 0], spd[..., 0, 1], spd[..., 0, 2],
                   spd[..., 1, 1], spd[..., 1, 2], spd[..., 2, 2]], -1)
    pts = f32(rng.random((n, 4, 3)))
    mask = rng.random(n) < 0.6
    v = f32(rng.standard_normal((3, n)))
    x = rng.integers(0, 3, n).astype(np.int32)

    f64 = lambda a: np.asarray(a, np.float64)
    e = f64(p1) - f64(p0)
    d = np.sqrt((e * e).sum(-1))
    r0, r1 = 1.0 / f64(h0), 1.0 / f64(h1)
    same = np.abs(r0 - r1) < 1e-6 * np.maximum(r0, r1)
    with np.errstate(divide="ignore", invalid="ignore"):
        lm = np.where(same, 0.5 * (r0 + r1),
                      (r1 - r0) / np.log(f64(h0) / f64(h1)))
    len_iso = d * lm
    M = f64(spd)
    l0 = np.sqrt(np.einsum("ni,nij,nj->n", e, M[0], e))
    l1 = np.sqrt(np.einsum("ni,nij,nj->n", e, M[1], e))
    len_ani = (2.0 / 3.0) * (l0 * l0 + l0 * l1 + l1 * l1) / (l0 + l1)
    P = f64(pts)
    d1, d2, d3 = P[:, 1] - P[:, 0], P[:, 2] - P[:, 0], P[:, 3] - P[:, 0]
    vol = np.einsum("ni,ni->n", d1, np.cross(d2, d3)) / 6.0
    pairs = ((0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3))
    ed = np.stack([P[:, j] - P[:, i] for i, j in pairs], 1)   # [n,6,3]

    def qual(l2sum, num):
        q = num / l2sum ** 1.5
        return np.where(vol > 0, np.minimum(q, 1.0), np.minimum(q, 0.0))
    q_iso = qual((ed * ed).sum((1, 2)), ALPHA_TET * vol)
    q_ani = qual(np.einsum("nei,nij,nej->n", ed, M[0], ed),
                 ALPHA_TET * vol * np.sqrt(np.linalg.det(M[0])))

    jit = jax.jit
    close = [
        ("edge_length_iso",
         jit(pk.edge_length_iso_pallas)(p0, p1, h0, h1), len_iso),
        ("edge_length_ani",
         jit(pk.edge_length_ani_pallas)(p0, p1, m6[0], m6[1]), len_ani),
        ("quality_iso", jit(pk.quality_pallas)(pts), q_iso),
        ("quality_ani", jit(pk.quality_pallas)(pts, m6[0]), q_ani),
    ]
    worst = {}
    for name, got, ref in close:
        got = np.asarray(got)
        if not (got.shape == ref.shape and got.dtype == np.float32
                and np.isfinite(got).all()):
            fail(f"kernel {name}: wrong shape or dtype, or non-finite values")
        worst[name] = float(np.max(np.abs(got - ref)
                                   / np.maximum(np.abs(ref), 1e-3)))
        if worst[name] > KERNEL_RTOL:
            fail(f"kernel {name} is {worst[name]:.3g} (relative) away "
                 f"from the float64 reference, limit {KERNEL_RTOL}")
    s, c = jit(pk.score_count_pallas)(mask, v[0])
    s3, c3 = jit(pk.score3_count_pallas)(mask, v[0], v[1], v[2])
    exact = [
        ("score_count", s, np.where(mask, -v[0], -np.inf)),
        ("score_count.n", c, np.int32(mask.sum())),
        ("score3_count", s3, np.where(
            mask, -np.minimum(v[0], np.minimum(v[1], v[2])), -np.inf)),
        ("score3_count.n", c3, np.int32(mask.sum())),
        ("merge_prefix", jit(pk.merge_prefix_pallas)(x),
         np.cumsum(x, dtype=np.int32)),
    ]
    for name, got, ref in exact:
        if not np.array_equal(np.asarray(got), ref):
            fail(f"kernel {name} is not bit-identical to its reference")
    return {**worst, "score_count": 0.0, "score3_count": 0.0,
            "merge_prefix": 0.0}


def group_block_kernels() -> dict:
    """tpu_custom_call count per Pallas kernel in the lowered text of a
    group block this run compiled (lowered again at the shapes the
    compile ledger recorded for its last call)."""
    import jax
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.parallel import groups
    from parmmg_tpu.parallel.distribute import split_to_shards
    from parmmg_tpu.utils.compilecache import LEDGER
    from parmmg_tpu.utils.fixtures import cube_mesh
    if not groups._GROUP_BLOCK_CACHE:
        fail("the run compiled no group block: it was not grouped")
    # a tiny call tree gives the argument STRUCTURE; the ledger's last
    # key gives every leaf's real shape and dtype
    vert, tet = cube_mesh(1)
    m = make_mesh(vert, tet, capP=64, capT=64)
    stacked, met_s = split_to_shards(
        m, jnp.ones(m.capP, m.vert.dtype), np.zeros(len(tet), np.int32), 1)
    args = (stacked, met_s, jnp.int32(0), jnp.ones(1, bool),
            jnp.asarray(True), jnp.asarray(True))
    leaves, treedef = jax.tree_util.tree_flatten(args)
    key = LEDGER._entries["groups.adapt_block"].last_key
    if len(key) != len(leaves):
        fail("group block argument structure changed under chip_smoke")
    sds = [jax.ShapeDtypeStruct(shape, np.dtype(dt)) for shape, dt in key]
    # the one block program holds every kernel of the cycle
    fn = next(iter(groups._GROUP_BLOCK_CACHE.values())).__wrapped__
    txt = fn.lower(*jax.tree_util.tree_unflatten(treedef, sds)).as_text()
    calls = re.findall(r'custom_call @tpu_custom_call\(.*?kernel_name = '
                       r'"([^"]+)"', txt)
    return {k: calls.count(k) for k in sorted(set(calls))}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
class _Tee(io.StringIO):
    """Keep what the CLI prints and pass it on as it comes: a run cut by
    a limit still shows how far it got."""

    def write(self, text):
        sys.__stdout__.write(text)
        sys.__stdout__.flush()
        return super().write(text)


def run_cli(argv: list[str]) -> tuple[int, dict]:
    """parmmg_tpu.cli.main in-process; returns (rc, -bench-json record)."""
    from parmmg_tpu import cli
    buf = _Tee()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    rec = {}
    for line in buf.getvalue().splitlines():
        if line.startswith("{") and '"ntets"' in line:
            rec = json.loads(line)
    return rc, rec


def watch_host_memory(period_s: float = 60.0):
    """Print the process's resident memory once a minute (daemon
    thread): the TPU compiler's host RAM is what a cold run exhausts
    first, and a killed run should show how close it came.  Returns the
    function that stops it."""
    import threading
    stop = threading.Event()
    t0 = time.perf_counter()

    def loop():
        while not stop.wait(period_s):
            with open("/proc/self/status") as f:
                kb = next(int(line.split()[1]) for line in f
                          if line.startswith("VmRSS"))
            say(f"  [host RSS {kb / 2 ** 20:.1f} GB at "
                f"{time.perf_counter() - t0:.0f} s]")
    thread = threading.Thread(target=loop, daemon=True)
    thread.start()

    def finish():
        stop.set()
        thread.join(5.0)
    return finish


def resilience_counters() -> dict:
    from parmmg_tpu.obs.metrics import REGISTRY
    snap = REGISTRY.snapshot()["counters"]
    return {k: v for k, v in snap.items() if k.startswith("resilience.")}


def phase_one_chip(args, device) -> None:
    import jax
    from parmmg_tpu import native
    from parmmg_tpu.parallel.groups import how_many_groups
    from parmmg_tpu.utils.compilecache import (LEDGER, default_cache_dir,
                                               ledger_violations)
    t0 = time.perf_counter()
    native_built = bool(native.build())
    mesh_p, sol_p, nv, nt = write_input(args.out_dir, args.seed)
    ngroups = how_many_groups(nt, MESH_SIZE)
    say(f"input: cube_mesh({N_CUBE}) seed {args.seed}: {nv} vertices, "
        f"{nt} tets, {ngroups} groups at -mesh-size {MESH_SIZE}")
    if ngroups < 2:
        fail("input too small for the grouped path")
    kernels = check_kernels(args.seed)
    t_setup0 = time.perf_counter() - t0

    out_p = os.path.join(args.out_dir, "cube.o.mesh")
    t1 = time.perf_counter()
    rc, rec = run_cli(
        ["-in", mesh_p, "-sol", sol_p, "-out", out_p, "-mesh-size",
         str(MESH_SIZE), "-niter", str(NITER), "-v", "5",
         "-bench-json"])
    wall = time.perf_counter() - t1
    if rc != 0:
        fail(f"cli.main returned {rc} (PMMG_SUCCESS is 0)")
    bad = {k: v for k, v in resilience_counters().items() if v}
    if bad:
        fail(f"resilience ladder was used: {bad}")

    chk = check_output_mesh(out_p)
    if chk["ntets"] != rec.get("ntets"):
        fail(f"file holds {chk['ntets']} tets, the run reported "
             f"{rec.get('ntets')}")
    if min(chk["qmin"], rec["qmin"]) <= QMIN_FLOOR:
        fail(f"qmin {chk['qmin']:.4f} (file) / {rec['qmin']:.4f} (run) "
             f"not above the floor {QMIN_FLOOR}")
    in_block = group_block_kernels()
    missing = [k for k in KERNELS if not in_block.get(k)]
    if missing:
        fail(f"no tpu_custom_call in the group block for {missing} "
             f"(found {in_block})")
    over = [v for v in ledger_violations() if v.startswith("groups.")]
    if over:
        fail(f"compile ledger over budget: {over}")

    snap = LEDGER.snapshot()
    compile_s = sum(r["compile_s"] for r in snap.values())
    grp = {k: (r["variants"], r["compile_s"]) for k, r in snap.items()
           if k.startswith("groups.") and r["calls"]}
    # (groups, capT) of every block shape that compiled: the first
    # [G, capT, 4] leaf of a call key is the stacked tet array
    shapes = sorted({next(shp[:2] for shp, _ in key
                          if len(shp) == 3 and shp[2] == 4)
                     for key in LEDGER._entries[
                         "groups.adapt_block"].keys_compiled})
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_cache_dir()
    # where the wall time went: the driver's phase timers (host-staged
    # phases, utils/placement.py) and the dispatch-to-pull seconds of
    # the cycle blocks, less their compile — the only device work
    from parmmg_tpu.obs.metrics import REGISTRY
    from parmmg_tpu.obs.trace import TRACER, replay_totals
    phase, _ = replay_totals(TRACER.ring)
    host_names = ("analysis", "metric", "bad-element polish",
                  "sequential repair", "fem conformity",
                  "metric and fields interpolation")
    host_s = {k: round(phase[k], 1) for k in host_names if k in phase}
    blocks_s = REGISTRY.snapshot()["counters"].get(
        "groups.pipeline.compute_s", 0.0)
    blocks_compile_s = snap["groups.adapt_block"]["compile_s"]
    device_s = blocks_s - blocks_compile_s
    if device_s <= 0:
        fail("no time was spent in cycle blocks on the device")
    say(f"output: {chk['nvert']} vertices, {chk['ntets']} tets, qmin "
        f"{chk['qmin']:.4f} qmean {chk['qmean']:.4f} (file, Euclidean); "
        f"run reported qmin {rec['qmin']:.4f} qmean {rec['qmean']:.4f}; "
        f"ops split/collapse/swap {rec['nsplit']}/{rec['ncollapse']}/"
        f"{rec['nswap']}; every interior face matched")
    say(f"seconds: {wall:.1f} in cli.main, of which {compile_s:.1f} "
        f"backend compile (set-up) and {wall - compile_s:.1f} the rest "
        f"(staging, adapt, tail, IO); {t_setup0:.1f} before it (input, "
        "native build, kernel checks)")
    say(f"shares of cli.main: cycle blocks on the chip {device_s:.1f} s "
        f"({device_s / wall:.1%}), their compile {blocks_compile_s:.1f} s "
        f"({blocks_compile_s / wall:.1%}), host-staged phases "
        f"{sum(host_s.values()):.1f} s ({sum(host_s.values()) / wall:.1%}) "
        f"{host_s}, the rest (group split/merge on the host, interface "
        f"displacement, IO) "
        f"{wall - blocks_s - sum(host_s.values()):.1f} s")
    say(f"compile ledger groups.* (shape variants, compile seconds): {grp}; "
        f"block shapes (groups, capT): {shapes}")
    say("pallas kernels against float64 on the chip, worst relative "
        f"error: { {k: float(f'{v:.2g}') for k, v in kernels.items()} }; "
        f"tpu_custom_call in the group block: {in_block}")
    # what the persistent cache holds after the run: a block program is
    # hundreds of MB of code, and a cache with a size limit drops what
    # does not fit
    entries = sorted(((os.path.getsize(os.path.join(cache, f)), f)
                      for f in os.listdir(cache)), reverse=True) \
        if os.path.isdir(cache) else []
    say(f"cache holds {len(entries)} entries, "
        f"{sum(sz for sz, _ in entries) / 2 ** 20:.1f} MiB (size limit "
        f"{jax.config.jax_compilation_cache_max_size}); largest: "
        f"{[(f.split('-')[0], round(sz / 2 ** 20, 1)) for sz, f in entries[:3]]}")
    say(f"cache directory: {cache}; native meshkit built: {native_built}; "
        f"device memory peak: "
        f"{(jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')}")
    with open(os.path.join(args.out_dir, "one_chip.json"), "w") as f:
        json.dump({"n": N_CUBE, "h": H_SHOCK, "seed": args.seed,
                   "ntets": chk["ntets"], "qmin": chk["qmin"],
                   "device": device}, f)
    for path in (mesh_p, sol_p, out_p, out_p[:-5] + ".sol"):
        if os.path.exists(path):        # tens of MB of ASCII, checked above
            os.remove(path)


def phase_four_chips(args, device) -> None:
    """The SPMD path (-ndev 4, parallel/dist.py) and what it is compared
    with, nothing else."""
    if device["count"] < 4:
        fail(f"--four-chips needs 4 devices, jax reports {device['count']}")
    mesh_p, sol_p, nv, nt = write_input(args.out_dir, args.seed)
    say(f"input: cube_mesh({N_CUBE}) seed {args.seed}: {nv} vertices, "
        f"{nt} tets")
    # the comparison: the one-device result on the same input, from the
    # file a previous one-chip phase wrote, else recomputed on device 0
    ref_p = os.path.join(args.out_dir, "one_chip.json")
    ref = None
    if os.path.exists(ref_p):
        with open(ref_p) as f:
            ref = json.load(f)
        if (ref.get("n"), ref.get("h"), ref.get("seed")) != \
                (N_CUBE, H_SHOCK, args.seed):
            ref = None
    if ref is None:
        out1 = os.path.join(args.out_dir, "cube.o1.mesh")
        rc, _ = run_cli(["-in", mesh_p, "-sol", sol_p, "-out", out1,
                            "-mesh-size", str(MESH_SIZE), "-niter",
                            str(NITER), "-v", "1"])
        if rc != 0:
            fail(f"one-device reference run returned {rc}")
        ref = check_output_mesh(out1)
        say(f"one-device reference recomputed: {ref['ntets']} tets")
    else:
        say(f"one-device reference from {ref_p}: {ref['ntets']} tets")

    # shards on four distinct devices: watch the code that places them
    seen: set = set()
    placed = _watch_shard_devices(seen)
    out4 = os.path.join(args.out_dir, "cube.o4.mesh")
    t1 = time.perf_counter()
    with placed:
        rc, rec = run_cli(
            ["-in", mesh_p, "-sol", sol_p, "-out", out4, "-ndev", "4",
             "-mesh-size", str(MESH_SIZE),      # ranks x groups, as the
             # cell spmd4-iso-growth: IParam.nDevices 4, two groups a rank
             "-niter", str(NITER), "-v", "5", "-bench-json"])
    wall = time.perf_counter() - t1
    if rc != 0:
        fail(f"cli.main -ndev 4 returned {rc}")
    if len(seen) != 4:
        fail(f"the SPMD path placed shards on devices {sorted(seen)}")
    bad = {k: v for k, v in resilience_counters().items() if v}
    if bad:
        fail(f"resilience ladder was used: {bad}")
    chk = check_output_mesh(out4)
    if chk["qmin"] <= QMIN_FLOOR:
        fail(f"qmin {chk['qmin']:.4f} not above the floor {QMIN_FLOOR}")
    if abs(chk["ntets"] - ref["ntets"]) > \
            NTET_RTOL * max(chk["ntets"], ref["ntets"]):
        fail(f"four-chip tet count {chk['ntets']} differs from the "
             f"one-device {ref['ntets']} by more than {NTET_RTOL:.0%}")
    say(f"output: {chk['ntets']} tets (one device: {ref['ntets']}), qmin "
        f"{chk['qmin']:.4f} qmean {chk['qmean']:.4f}; shards on devices "
        f"{sorted(seen)}; {wall:.1f} s in cli.main")


@contextlib.contextmanager
def _watch_shard_devices(seen: set):
    """Check where dist.shard_stacked places its leaves, as it places
    them: code that has only ever seen one real chip may put every shard
    on the first.  The answer is printed at the first placement, before
    the first SPMD compile, and a wrong one ends the run there."""
    import jax
    from parmmg_tpu.parallel import dist
    orig = dist.shard_stacked

    def wrapped(stacked, dmesh):
        out = orig(stacked, dmesh)
        for leaf in jax.tree_util.tree_leaves(out):
            ids = sorted(d.id for d in leaf.devices())
            per_dev = sorted(sh.device.id for sh in leaf.addressable_shards
                             if sh.data.shape[0] > 0)
            if len(set(ids)) != 4 or per_dev != ids:
                fail(f"a sharded leaf of shape {leaf.shape} sits on devices "
                     f"{ids} (rows on {per_dev}), not on four distinct ones")
        if not seen:
            say("shards placed on four distinct devices: "
                f"{sorted(d.id for d in dmesh.devices.flat)}")
        seen.update(d.id for d in dmesh.devices.flat)
        return out
    dist.shard_stacked = wrapped
    try:
        yield
    finally:
        dist.shard_stacked = orig


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir",
                    default=os.path.join(HERE, "chiprun_out", "chip_smoke"))
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the -ndev 4 SPMD path and its comparison")
    args = ap.parse_args()

    sys.path.insert(0, HERE)
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform {d.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    import parmmg_tpu  # noqa: F401 — fails here in a bare directory
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices())}
    os.makedirs(args.out_dir, exist_ok=True)
    say(f"device: {device}; jax {jax.__version__}")
    stop_watching = watch_host_memory()
    if args.four_chips:
        phase_four_chips(args, device)
    else:
        if device["count"] != 1:
            say(f"note: {device['count']} devices visible; this phase "
                "uses the first")
        phase_one_chip(args, device)
    stop_watching()                 # nothing may follow the result line
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
