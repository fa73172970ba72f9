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

Sections (arguments; ``main`` alone by default): ``main`` the list above,
``payload`` the scatter's and the gather's cost by payload width,
``gathers`` the two prices of a gather at the cells' own shapes (PR 42),
``perms`` a fetch or a scatter through a permutation the program has just
sorted against the sort that carries the payload itself (PR 45).

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


# the cells' own shapes (BENCHMARK.json: a group of `iso-growth` is
# capP 8516, capT 43118; the edge table is 6 x capT wide)
CELL_P = 8516
CELL_T = 43118
GATHER_REPS = int(os.environ.get("MB_GATHER_REPS", "200"))
# run only the gather cases whose name starts with this
GATHER_ONLY = os.environ.get("MB_GATHER_ONLY", "")


def gather_prices():
    """The two prices of a gather on this chip (PR 42): ONE scalar out of
    a 1-D per-vertex table against a ROW out of a ``[capP, k]`` table at
    the same indices, at the cells' own shapes.  Each case is
    GATHER_REPS gathers chained through the index vector in one
    ``fori_loop``; ``chain`` is the loop with no gather in it (the
    elementwise pass that carries the dependency), to be subtracted.

    Read on one v5e (PR 42's first chip call; ms a gather, sorted ``_e``
    and random ``_r`` indices alike to 0.002; ``chain`` 0.005):

    =========================================  =======  ===========
    258,708 indices (6 x capT) into capP rows  ms       ns an index
    =========================================  =======  ===========
    ``u32[8516]`` / ``f32``, 1-D               1.728    6.7
    ``pred[8516]``, 1-D                        1.976    7.6
    ``u32[8516, 1]``                           1.728    6.7
    rows of 2, 4, 8, 12, 16 ``u32`` / ``f32``  0.541-3  2.1
    the same table stacked in the program      0.537-2  2.1
    =========================================  =======  ===========

    ``[43118, 4]`` indices: 1.313 (``pred`` 1.480) against 0.406 for a
    row of 3, 4 or 8; ``[43118]``: 0.291 against 0.090; a per-TET
    ``u32[43118]`` at 258,708 indices: 1.729 against 1.209 for a row of
    four.  So a row of a per-vertex table costs 0.31 of one scalar
    whatever it holds up to sixteen words, a row of ONE word costs the
    scalar's price (``ops/rowpack`` gives a lone column its own copy for
    company), and a per-tet table has no cheap row.  The table
    TRANSPOSED, ``[k, capP]`` gathered into ``[k, 258708]``: 0.536 for
    k = 4 and for k = 11 (PR 42's call 5): the row's price, with the
    indices on the lanes."""
    global K
    K = GATHER_REPS
    # dozens of small programs: keep them out of the machine's capped
    # compile cache, where they could evict a block program
    jax.config.update("jax_enable_compilation_cache", False)
    P, T = CELL_P, CELL_T
    print(f"\ngather prices (backend={jax.default_backend()} capP={P} "
          f"capT={T} reps={K})")
    rng = np.random.default_rng(0)
    # an edge table's endpoints are sorted by (a, b); a tet's corners
    # are not
    idx_e = jnp.asarray(np.sort(rng.integers(0, P, 6 * T)), jnp.int32)
    idx_r = jnp.asarray(rng.integers(0, P, 6 * T), jnp.int32)
    idx_t4 = jnp.asarray(rng.integers(0, P, (T, 4)), jnp.int32)
    idx_t = jnp.asarray(rng.integers(0, P, T), jnp.int32)
    idx_pt = jnp.asarray(rng.integers(0, T, 6 * T), jnp.int32)
    cols = [jnp.asarray(rng.integers(0, 2 ** 32, P, dtype=np.uint64)
                        .astype(np.uint32)) for _ in range(16)]
    colsT = [jnp.asarray(rng.integers(0, 2 ** 32, T, dtype=np.uint64)
                         .astype(np.uint32)) for _ in range(4)]
    u32 = jnp.uint32
    f32 = jnp.float32
    bc = jax.lax.bitcast_convert_type

    def fold(g, ndim):
        """One word a result row: every column is used."""
        if g.dtype == jnp.bool_:
            return g.astype(u32)
        if g.dtype != u32:
            g = bc(g, u32)
        if g.ndim > ndim:
            g = jax.lax.reduce(g, u32(0), jax.lax.bitwise_xor,
                               (g.ndim - 1,))
        return g

    def case(name, fetch, idx, rows=P):
        """``fetch(i, idx)`` chained through ``idx``, indices into a
        table of ``rows``."""
        if not name.startswith(GATHER_ONLY):
            return
        # 0xFFFFFFFF xor-folds out of random words about never: the
        # index stays what it was, and the compiler cannot know
        def body(i, idx):
            w = fold(fetch(i, idx), idx.ndim)
            return jnp.where(w == u32(0xFFFFFFFF), idx + 1, idx) \
                % jnp.int32(rows)
        dt = timed(name, loop(body), idx)
        print(f"{'':14s} {dt * 1e9 / idx.size:9.2f} ns an index")

    def table(k, dtype=u32):
        t = jnp.stack(cols[:k], axis=1)
        return t if dtype == u32 else bc(t, dtype)

    for tag, idx in (("e", idx_e), ("r", idx_r)):
        case(f"chain_{tag}", lambda i, x: x.astype(u32), idx)
        case(f"u32_1d_{tag}", lambda i, x: cols[0][x], idx)
        case(f"pred_1d_{tag}", lambda i, x: (cols[0] > 7)[x], idx)
        case(f"f32_1d_{tag}", lambda i, x: bc(cols[0], f32)[x], idx)
        case(f"u32_k1_{tag}", lambda i, x: cols[0][:, None][x], idx)
        for k in (2, 4, 8, 12, 16):
            tk = table(k)
            case(f"u32_k{k}_{tag}", lambda i, x, tk=tk: tk[x], idx)
        for k in (4, 8):
            tk = table(k, f32)
            case(f"f32_k{k}_{tag}", lambda i, x, tk=tk: tk[x], idx)
        # the table made INSIDE the timed program (the layout the
        # compiler gives a table it builds itself), from columns that
        # change every repetition
        for k in (4, 8):
            case(f"stack_k{k}_{tag}", lambda i, x, k=k: jnp.stack(
                [c + i.astype(u32) for c in cols[:k]], axis=1)[x], idx)
            case(f"stackf_k{k}_{tag}", lambda i, x, k=k: bc(jnp.stack(
                [c + i.astype(u32) for c in cols[:k]], axis=1), f32)[x],
                idx)
    # a tet's four corners ([T, 4] indices) and one vertex a tet ([T])
    case("chain_t4", lambda i, x: x.astype(u32), idx_t4)
    case("u32_1d_t4", lambda i, x: cols[0][x], idx_t4)
    case("pred_1d_t4", lambda i, x: (cols[0] > 7)[x], idx_t4)
    for k in (3, 4, 8):
        tk = table(k)
        case(f"u32_k{k}_t4", lambda i, x, tk=tk: tk[x], idx_t4)
    case("chain_t", lambda i, x: x.astype(u32), idx_t)
    case("u32_1d_t", lambda i, x: cols[0][x], idx_t)
    for k in (4, 8, 16):
        tk = table(k)
        case(f"u32_k{k}_t", lambda i, x, tk=tk: tk[x], idx_t)
    # the table transposed, ``[k, capP]`` gathered along its rows: the
    # result ``[k, N]`` has the indices on the lanes, where a row
    # result ``[N, k]`` pads k to 128 of them (PERF.md section 7, after
    # PR 42)
    for k in (4, 11):
        tk = table(k).T

        def columns(i, x, tk=tk):
            g = tk[:, x]
            return jax.lax.reduce(g, u32(0), jax.lax.bitwise_xor, (0,))
        case(f"rowsT_k{k}_e", columns, idx_e)
    # per-TET tables at the edge table's width
    case("chain_pt", lambda i, x: x.astype(u32), idx_pt, rows=T)
    case("tet_1d_pt", lambda i, x: colsT[0][x], idx_pt, rows=T)
    tT = jnp.stack(colsT, axis=1)
    case("tet_k4_pt", lambda i, x: tT[x], idx_pt, rows=T)


PERM_REPS = int(os.environ.get("MB_PERM_REPS", "50"))
# run only the perms cases whose name starts with this
PERM_ONLY = os.environ.get("MB_PERM_ONLY", "")


def perm_prices():
    """What a table maker pays to read, in sorted order, what it has just
    sorted (PR 45): ``argsort`` and a fetch through the permutation
    against ONE ``lax.sort`` that carries the payload as an operand; a
    sorted neighbour by ``x[partner]`` against two shifts; the way back
    (``.at[order].set``) against a second sort keyed on the permutation;
    the head scatter against a reverse segmented scan.  Widths are the
    cells' own: 258,708 (6 x capT, the edge table), 172,472 (4 x capT,
    the faces) and 64,680 (the collapse's band, 6 x 10,780).  Each case
    is PERM_REPS repetitions chained through its input in one
    ``fori_loop``; ``chain`` is the loop alone.

    Read on the chip and on its host in PR 45 (PERF.md section 5, "a
    permutation's two ways"): on the chip a payload operand costs the
    sort 0.07 ms and the fetch 1.7-4.4, the way back by a sort 0.42 and
    by the scatter 3.6; on XLA:CPU an operand costs the sort a quarter
    and a fetch or a scatter next to nothing.  The chip needs about 11
    minutes for the three widths at 30 repetitions."""
    global K
    K = PERM_REPS
    jax.config.update("jax_enable_compilation_cache", False)
    P, T = CELL_P, CELL_T
    print(f"\nperm prices (backend={jax.default_backend()} capP={P} "
          f"capT={T} reps={K})")
    rng = np.random.default_rng(0)
    i32, u32 = jnp.int32, jnp.uint32
    big = np.iinfo(np.int32).max
    sort = jax.lax.sort

    def fold(*cols):
        w = None
        for c in cols:
            c = c.astype(u32) if c.dtype != u32 else c
            w = c if w is None else w ^ c
        return w

    def case(name, step, x):
        """``step(x)`` returns the columns a formulation hands on; they
        are folded into one word a row that feeds ``x`` back."""
        if not name.startswith(PERM_ONLY):
            return

        def body(i, x):
            w = fold(*step(x))
            # 0xFFFFFFFF about never folds out: x stays what it was, and
            # the compiler cannot know
            return jnp.where(w == u32(0xFFFFFFFF), x + 1, x)
        timed(name, loop(body), x)

    def edge_keys(n):
        """Packed edge keys as a cycle's table meets them: each key about
        five times (a shell), a third of the slots dead (INT32_MAX)."""
        k = rng.integers(0, P * P, n // 5)[rng.integers(0, n // 5, n)]
        k = np.where(rng.random(n) < 0.33, big, k)
        return jnp.asarray(k, i32)

    for n in (6 * T, 4 * T, 64680):
        key = edge_keys(n)
        tag = jnp.asarray(rng.integers(0, 2 ** 16, n), u32)
        tag2 = jnp.asarray(rng.integers(0, 2 ** 16, n), i32)
        iota = jnp.arange(n, dtype=i32)
        case(f"chain_{n}", lambda k: (k,), key)
        case(f"argsort_{n}", lambda k: (jnp.argsort(k),), key)

        def old1(k):
            order = jnp.argsort(k)
            return order, k[order]
        case(f"argsort+1f_{n}", old1, key)

        def old2(k):
            order = jnp.argsort(k)
            return order, k[order], tag[order]
        case(f"argsort+2f_{n}", old2, key)

        def old3(k):
            order = jnp.argsort(k)
            return order, k[order], tag[order], tag2[order]
        case(f"argsort+3f_{n}", old3, key)
        case(f"sort_ki_{n}", lambda k: sort(
            (k, iota), num_keys=1, is_stable=True), key)
        case(f"sort_kit_{n}", lambda k: sort(
            (k, iota, tag), num_keys=1, is_stable=True), key)
        case(f"sort_kitt_{n}", lambda k: sort(
            (k, iota, tag, tag2), num_keys=1, is_stable=True), key)

        # two key columns (the faces; the unpacked edge branch)
        c0 = jnp.asarray(rng.integers(0, P, n), i32)

        def lex_old(w):
            order = jnp.lexsort((w, c0))
            return order, c0[order], w[order]
        case(f"lexsort+2f_{n}", lex_old, key)
        case(f"sort2_{n}", lambda w: sort(
            (c0, w, iota), num_keys=2, is_stable=True), key)

        # a sorted neighbour: x[partner] against the shifts
        first = np.ones(n, bool)
        first[1:] = rng.random(n - 1) < 0.5
        eq_next = jnp.asarray(~first[1:])
        same_next = jnp.concatenate([eq_next, jnp.array([False])])
        same_prev = jnp.concatenate([jnp.array([False]), eq_next])
        partner = jnp.where(same_next, iota + 1,
                            jnp.where(same_prev, iota - 1, iota))
        f = jnp.asarray(rng.integers(0, 4, n), i32)

        def twin_old(t):
            return t[partner], f[partner]

        def twin_new(t):
            def nb(x):
                up = jnp.concatenate([x[1:], x[-1:]])
                dn = jnp.concatenate([x[:1], x[:-1]])
                return jnp.where(same_next, up,
                                 jnp.where(same_prev, dn, x))
            return nb(t), nb(f)
        case(f"twin_fetch_{n}", twin_old, key)
        case(f"twin_shift_{n}", twin_new, key)

        # the way back: a permutation scatter of two columns against the
        # sort keyed on the permutation
        order = jnp.asarray(rng.permutation(n), i32)

        def back_old(p):
            pay = jnp.stack([p, tag2], axis=1)
            b = jnp.zeros((n, 2), i32).at[order].set(
                pay, unique_indices=True)
            return b[:, 0], b[:, 1]

        def back_new(p):
            _, b0, b1 = sort((order, p, tag2), num_keys=1)
            return b0, b1

        def back1_old(p):
            return (jnp.zeros(n, i32).at[order].set(
                p, unique_indices=True),)

        def back1_new(p):
            return (sort((order, p), num_keys=1)[1],)
        case(f"back_scatter2_{n}", back_old, key)
        case(f"back_sort2_{n}", back_new, key)
        case(f"back_scatter1_{n}", back1_old, key)
        case(f"back_sort1_{n}", back1_new, key)

        # the head table: a segment's total (at its last member) written
        # at its head, by a drop scatter against a reverse segmented scan
        firstj = jnp.asarray(first)
        is_last = jnp.concatenate([firstj[1:], jnp.array([True])])
        seg_head = jax.lax.associative_scan(
            jnp.maximum, jnp.where(firstj, iota, 0))

        def head_old(v):
            pay = jnp.stack([v, tag2], axis=1)
            h = jnp.zeros((n, 2), i32).at[
                jnp.where(is_last, seg_head, n)].set(
                pay, mode="drop", unique_indices=True)
            return h[:, 0], h[:, 1]

        def head_new(v):
            def comb(pa, pb):
                # reverse scan: pa is the element further RIGHT
                fa, va, wa = pa
                fb, vb, wb = pb
                return (fa | fb, jnp.where(fb, vb, va),
                        jnp.where(fb, wb, wa))
            _, h0, h1 = jax.lax.associative_scan(
                comb, (is_last, v, tag2), reverse=True)
            return jnp.where(firstj, h0, 0), jnp.where(firstj, h1, 0)
        case(f"head_scatter_{n}", head_old, key)
        case(f"head_rscan_{n}", head_new, key)

        def fwd_scan3(v):
            def comb(pa, pb):
                fa, ha, va = pa
                fb, hb, vb = pb
                return (fa | fb, jnp.where(fb, hb, jnp.maximum(ha, hb)),
                        jnp.where(fb, vb, va | vb))
            return jax.lax.associative_scan(
                comb, (firstj, jnp.where(firstj, iota, 0), v))[1:]
        case(f"fwd_scan3_{n}", fwd_scan3, key)


SECTIONS = {"main": main, "payload": payload_scaling,
            "gathers": gather_prices, "perms": perm_prices}

if __name__ == "__main__":
    for section in sys.argv[1:] or ["main"]:
        SECTIONS[section]()
