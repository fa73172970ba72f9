"""A polish wave judges only what changed (PR 35, ``ops/worklist``).

The merged polish hands its waves a worklist: which tet rows changed since
each of the two swap kernels (``swap_edges_wave``, ``swapgen_wave``) last
judged the mesh.  A kernel evaluates only the candidates with a changed
shell, in chunks as wide as that list, and the result is the full
evaluation's to the bit:

(a) eight waves with the list against eight with every row forced dirty
    in every wave (and against the wave that takes no list at all), scalar
    and tensor metric, a flat and a curved mesh under ``hausd``;
(b) what a gate beyond its own shell refused stays on the list: a ring
    swap that lost a claim, one cut for want of free rows, candidates
    past the budget, a 2-2 swap whose flipped diagonal exists elsewhere (a
    3-2 swap removes it, then the 2-2 applies); equal scores on a shared
    tet break as they did before the rows were permuted;
(c) a moved vertex puts its ball back on the list, a wave that changed
    nothing leaves an empty list and runs no chunk;
(d) the cycle block's program does not know any of this.
"""
import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.ops import worklist as wl
from parmmg_tpu.ops.adapt import adapt_cycle_impl, sliver_polish
from parmmg_tpu.ops.adjacency import boundary_edge_tags, build_adjacency
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.edges import claim_shells
from parmmg_tpu.ops.swap import swap_edges_wave
from parmmg_tpu.ops.swapgen import swapgen_wave
from parmmg_tpu.utils.fixtures import (analytic_ani_metric, cube_mesh,
                                       sphere_mesh)

N = 5                   # 750 tets, cube and ball alike: one program a metric
HAUSD = 0.01
WAVES = 8
# case -> (generator, jitter in cells, tensor metric?)
CASES = {
    "cube": (cube_mesh, 0.3, False),
    "cube-tensor": (cube_mesh, 0.3, True),
    "ball": (sphere_mesh, 0.2, False),
    "ball-tensor": (sphere_mesh, 0.2, True),
}


@functools.cache
def _on_host(case):
    gen, jitter, tensor = CASES[case]
    vert, tet = gen(N)
    ref, _ = cube_mesh(N)       # the ball is this cube, mapped
    inner = ((ref > 1e-9) & (ref < 1 - 1e-9)).all(axis=1)
    vert = vert.copy()
    span = vert.max() - vert.min()
    vert[inner] += np.random.default_rng(4).uniform(
        -jitter, jitter, (int(inner.sum()), 3)) * span / N
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    if tensor:
        h = analytic_ani_metric(vert / span, "shock")
        met = jnp.zeros((mesh.capP, 6), mesh.vert.dtype).at[
            :, jnp.array([0, 3, 5])].set(1.0)
        met = met.at[: len(h)].set(jnp.asarray(h, mesh.vert.dtype))
    else:
        met = jnp.full(mesh.capP, span / N, mesh.vert.dtype)
    return jax.tree.map(np.asarray, (mesh, met))


def fixture(case):
    """A fresh copy each call: ``sliver_polish`` donates its mesh."""
    return jax.tree.map(jnp.array, _on_host(case))


def same_mesh(a, b):
    return all(x.dtype == y.dtype and np.array_equal(x, y)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@functools.cache
def eight_waves(case):
    """Per wave [(mesh, counts) with the list carried, (mesh, counts)
    with every row forced dirty], each chain on its own mesh."""
    mesh, met = fixture(case)
    forced, _ = fixture(case)
    carried = wl.all_dirty(mesh)
    out = []
    for w in range(WAVES):
        wave = jnp.asarray(1000 + w, jnp.int32)
        mesh, counts, carried = sliver_polish(
            mesh, met, wave, hausd=HAUSD, worklist=carried)
        forced, fcounts, _ = sliver_polish(
            forced, met, wave, hausd=HAUSD, worklist=wl.all_dirty(forced))
        out.append(jax.tree.map(np.array, ((mesh, counts),
                                           (forced, fcounts))))
    return out


# ---- (a) ------------------------------------------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_the_list_gives_what_every_row_forced_dirty_gives(case):
    for w, ((mesh, counts), (forced, fcounts)) in enumerate(
            eight_waves(case)):
        for path, a in jax.tree_util.tree_leaves_with_path(mesh):
            b = dict(jax.tree_util.tree_leaves_with_path(forced))[path]
            assert a.dtype == b.dtype and np.array_equal(a, b), \
                f"{case} wave {w}: leaf {jax.tree_util.keystr(path)}"
        assert counts[:9].tolist() == fcounts[:9].tolist()
    # the waves did work, on the surface too where there is one
    total = np.sum([c for (_, c), _ in eight_waves(case)], axis=0)
    assert total[1] > 20 and total[2] > 20
    if case.endswith("tensor"):
        assert total[0] > 0 and total[4] > 0    # collapses, hausd vetoes
    if case.startswith("ball"):
        assert total[5] > 0                     # surface vertices slid


@pytest.mark.parametrize("case", list(CASES))
def test_the_counts_say_what_was_judged(case):
    rows = eight_waves(case)
    for w, ((_, counts), (_, fcounts)) in enumerate(rows):
        cand, listed = int(counts[9]), int(counts[10])
        assert len(counts) == 11 and 0 < listed <= cand
        # forced dirty, a kernel judges all its top-K selected
        assert int(fcounts[10]) == int(fcounts[9]) == cand
        if w == 0:
            assert listed == cand
    # from the second wave on the list is a part of the candidates
    assert all(int(c[10]) < int(c[9]) for (_, c), _ in rows[1:])


def test_the_list_gives_what_no_list_gives():
    """The same eight waves through the program that takes no list (the
    one ``adapt_mesh`` and the grouped polish run)."""
    mesh, met = fixture("cube-tensor")
    for w, ((listed, counts), _) in enumerate(eight_waves("cube-tensor")):
        mesh, plain = sliver_polish(
            mesh, met, jnp.asarray(1000 + w, jnp.int32), hausd=HAUSD)
        assert same_mesh(listed, mesh), f"wave {w}"
        assert np.asarray(plain)[:9].tolist() == counts[:9].tolist()
        assert np.asarray(plain)[9:].tolist() == [0, 0]     # not counted


# ---- (b) ------------------------------------------------------------------

def _exists_fixture():
    """A flat boundary quad (a, x0, b, x1) over two tets whose long
    diagonal (a, b) a 2-2 swap would flip to (x0, x1), and three thin
    tets round an edge (x0, x1) that is already there: the 2-2 swap
    passes every gate of its own shell and ``exists`` alone refuses it,
    until a 3-2 swap of those three removes that edge."""
    r = 0.3
    vert = np.array([
        [0, -2, 0], [0, 2, 0], [-0.6, 0, 0], [0.6, 0, 0], [0, 0, -1],
        [0, r, 0], [0, -r / 2, 0.866 * r], [0, -r / 2, -0.866 * r]], float)
    a, b, x0, x1, c, e, f, g = range(8)
    tet = np.array([[a, b, c, x0], [b, a, c, x1], [x0, x1, e, f],
                    [x0, x1, f, g], [x0, x1, g, e]], np.int32)
    mesh = boundary_edge_tags(build_adjacency(
        make_mesh(vert, tet, capP=16, capT=16)))
    return mesh, jnp.ones(mesh.capP, mesh.vert.dtype)


def test_a_swap_that_exists_alone_refused_stays_listed_and_applies():
    mesh, met = _exists_fixture()
    kernel = jax.jit(functools.partial(swap_edges_wave, budget_div=1))
    first = kernel(mesh, met, worklist=wl.all_dirty(mesh).edges)
    # the 3-2 swap applied (row 4 died), the 2-2 did not
    assert int(first.nswap) == 1
    assert np.asarray(first.mesh.tmask)[:5].tolist() == [1, 1, 1, 1, 0]
    assert np.array_equal(np.asarray(first.mesh.tet)[:2],
                          np.asarray(mesh.tet)[:2])
    assert bool(first.keep[0])       # the 2-2 swap's first shell row
    changed = wl.changes(mesh, first.mesh)
    # nothing of the 2-2 swap's shell changed: its rows, its edge's ends
    assert not np.asarray(changed.rows)[:2].any()
    assert not np.asarray(changed.verts)[:2].any()
    full = kernel(first.mesh, met)
    assert int(full.nswap) == 1      # the 2-2 swap, now
    listed = kernel(first.mesh, met, worklist=wl.Dirty(
        changed.rows | first.keep, changed.verts))
    assert int(listed.nswap) == 1 and same_mesh(listed.mesh, full.mesh)
    # and it is ``keep`` that did it
    unkept = kernel(first.mesh, met, worklist=changed)
    assert int(unkept.nswap) == 0


def _tight_fixture(spare: int = 8):
    """The jittered cube with NO free row: capacity is its tets plus
    ``spare`` live tets that touch nothing (their own vertices, far
    away).  Killing those is the operation elsewhere that frees rows."""
    mesh, met = _on_host("cube")
    n_t = int(mesh.tmask.sum())
    n_p = int(mesh.vmask.sum())
    vert = np.asarray(mesh.vert)[:n_p]
    unit = np.array([[0, 0, 0], [1, 0, 0], [0, 1, 0], [0, 0, 1]], float)
    far = np.concatenate([unit + [10 + 2 * i, 0, 0] for i in range(spare)])
    tet = np.concatenate([
        np.asarray(mesh.tet)[:n_t],
        n_p + np.arange(4 * spare, dtype=np.int32).reshape(spare, 4)])
    tight = analyze_mesh(make_mesh(
        np.concatenate([vert, far]), tet, capP=mesh.vert.shape[0],
        capT=n_t + spare)).mesh
    return tight, jnp.asarray(met), n_t


def test_a_ring_swap_cut_for_want_of_rows_stays_listed_and_applies():
    tight, met, n_t = _tight_fixture()
    kernel = jax.jit(functools.partial(swapgen_wave, budget_div=1))
    first = kernel(tight, met, worklist=wl.all_dirty(tight).rings)
    assert same_mesh(first.mesh, kernel(tight, met).mesh)
    # only rings of four fit (they reuse their shell's rows)
    assert int(first.nswap) > 0
    assert int(first.mesh.tmask.sum()) == n_t + 8
    freed = dataclasses.replace(
        first.mesh, tmask=first.mesh.tmask.at[n_t:].set(False))
    changed = wl.changes(tight, freed)
    full = kernel(freed, met)
    # with rows to take, rings of five and six apply: the mesh grows
    assert int(full.mesh.tmask.sum()) > n_t
    listed = kernel(freed, met, worklist=wl.Dirty(
        changed.rows | first.keep, changed.verts))
    assert same_mesh(listed.mesh, full.mesh)
    assert int(listed.nswap) == int(full.nswap)
    assert int(listed.nlist) < int(listed.ncand)
    unkept = kernel(freed, met, worklist=changed)
    assert not same_mesh(unkept.mesh, full.mesh)


def test_a_claim_loser_stays_listed_and_applies():
    """Free rows for every ring and budget for every candidate: what the
    first look keeps lost a claim, some of it to a candidate that lost
    its own, so nothing of its shell changed."""
    mesh, met = fixture("cube")
    kernel = jax.jit(functools.partial(swapgen_wave, budget_div=1))
    first = kernel(mesh, met, worklist=wl.all_dirty(mesh).rings)
    assert int(first.nswap) > 0
    assert int(first.ncand) < 2 * int((~first.mesh.tmask).sum())
    changed = wl.changes(mesh, first.mesh)
    assert bool(jnp.any(first.keep & ~changed.rows))
    full = kernel(first.mesh, met)
    listed = kernel(first.mesh, met, worklist=wl.Dirty(
        changed.rows | first.keep, changed.verts))
    assert int(full.nswap) > 0 and same_mesh(listed.mesh, full.mesh)
    assert int(listed.nlist) < int(listed.ncand)
    unkept = kernel(first.mesh, met, worklist=changed)
    assert not same_mesh(unkept.mesh, full.mesh)


@pytest.mark.parametrize("kernel", [swap_edges_wave, swapgen_wave])
def test_candidates_past_the_budget_put_every_row_back(kernel):
    mesh, met = fixture("cube")
    part = wl.all_dirty(mesh).edges
    tiny = jax.jit(functools.partial(kernel, budget=16))(
        mesh, met, worklist=part)
    assert int(tiny.ncand) == 16 == int(tiny.nlist)
    assert bool(jnp.all(tiny.keep))
    wide = jax.jit(functools.partial(kernel, budget_div=1))(
        mesh, met, worklist=part)
    assert int(wide.ncand) > 16 and not bool(jnp.all(wide.keep))


def test_equal_scores_on_a_shared_tet_break_as_before_the_permutation():
    """``tie_hash`` is taken of a candidate's position and is not
    monotone: permuted candidates carry the position they had."""
    n, capT = 64, 40
    score = jnp.ones(n)                     # every score ties
    cand = jnp.ones(n, bool)
    rng = np.random.default_rng(0)
    shells = tuple(jnp.asarray(rng.integers(0, capT, n), jnp.int32)
                   for _ in range(3))       # crowded: most share a tet
    win = np.asarray(claim_shells(score, cand, shells, capT))
    assert 0 < win.sum() < n
    listed = jnp.asarray(rng.random(n) < 0.5)
    pos, nl = wl.listed_first(listed)
    pos = np.asarray(pos)
    assert int(nl) == int(listed.sum())
    assert np.asarray(listed)[pos[:int(nl)]].all()
    assert (np.diff(pos[:int(nl)]) > 0).all()       # stable, both parts
    assert (np.diff(pos[int(nl):]) > 0).all()
    moved = [s[pos] for s in shells]
    carried = np.asarray(claim_shells(score[pos], cand[pos], moved, capT,
                                      pos=jnp.asarray(pos)))
    assert np.array_equal(carried, win[pos])
    rehashed = np.asarray(claim_shells(score[pos], cand[pos], moved, capT))
    assert not np.array_equal(rehashed, win[pos])


# ---- (c) ------------------------------------------------------------------

def _clean_cube():
    vert, tet = cube_mesh(N)
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    return mesh, jnp.full(mesh.capP, 1.0 / N, mesh.vert.dtype)


def test_a_wave_that_changed_nothing_leaves_an_empty_list():
    mesh, met = _clean_cube()
    carried = wl.all_dirty(mesh)
    for w in range(2):
        mesh, counts, carried = sliver_polish(
            mesh, met, jnp.asarray(1000 + w, jnp.int32), hausd=HAUSD,
            worklist=carried)
        counts = np.asarray(counts).tolist()
        assert counts[:3] == [0, 0, 0] and counts[9] > 0
        assert counts[10] == (counts[9] if w == 0 else 0)
        assert not any(bool(jnp.any(x)) for x in jax.tree.leaves(carried))


def test_a_moved_vertex_puts_its_ball_back_on_the_list():
    mesh, met = _clean_cube()
    v = int(np.argmin(np.abs(np.asarray(mesh.vert) - 0.5).sum(axis=1)))
    after = dataclasses.replace(
        mesh, vert=mesh.vert.at[v].add(jnp.asarray([0.02, 0.01, 0.0])))
    changed = wl.changes(mesh, after)
    ball = np.asarray(mesh.tmask) & (np.asarray(mesh.tet) == v).any(axis=1)
    assert ball.sum() > 4 and np.array_equal(np.asarray(changed.rows), ball)
    assert not bool(jnp.any(changed.verts))
    kernel = jax.jit(functools.partial(swapgen_wave, budget_div=1))
    listed = kernel(after, met, worklist=changed)
    full = kernel(after, met)
    assert 0 < int(listed.nlist) < int(listed.ncand)
    assert same_mesh(listed.mesh, full.mesh)
    # every listed candidate's shell holds a tet of the ball: the edges of
    # the ball's tets, no more
    edges = {tuple(sorted((int(t[i]), int(t[j]))))
             for t in np.asarray(mesh.tet)[ball]
             for i in range(4) for j in range(i + 1, 4)}
    assert int(listed.nlist) <= len(edges)


def test_the_stage_runs_as_many_chunks_as_the_list_fills():
    ran = []

    def stage(sel):
        jax.debug.callback(lambda s: ran.append(int(s[0])), sel)
        return {"twice": 2 * sel}

    k, chunks = 100, 8          # chunks of 13 rows; the last overlaps
    sel = jnp.arange(k, dtype=jnp.int32)
    run = jax.jit(lambda nl: wl.staged(stage, sel, nl, chunks))
    for nl, starts in ((0, []), (1, [0]), (13, [0]), (14, [0, 13]),
                       (100, [0, 13, 26, 39, 52, 65, 78, 87])):
        del ran[:]
        out = np.asarray(jax.block_until_ready(run(nl))["twice"])
        jax.effects_barrier()
        assert sorted(ran) == starts, nl
        done = min(k, 13 * len(starts))
        assert np.array_equal(out[:done], 2 * np.arange(done))
        assert not out[done:].any()


# ---- (d) ------------------------------------------------------------------

def test_the_cycle_block_lowers_to_the_program_it_was(monkeypatch):
    """``adapt_cycle_impl`` hands its swap kernel no list; with the new
    argument left at None the kernel traces what it always did, so the
    block program's text is the same whether or not the argument is
    named, and holds nothing of the list."""
    from parmmg_tpu.ops import adapt
    mesh, met = _clean_cube()
    wave = jnp.asarray(0, jnp.int32)

    def text():
        return jax.jit(functools.partial(
            adapt_cycle_impl, do_swap=True, hausd=HAUSD)).lower(
                mesh, met, wave).as_text()

    plain = text()
    monkeypatch.setattr(adapt, "swap_edges_wave", functools.partial(
        swap_edges_wave, worklist=None))
    assert text() == plain
    calls = []
    monkeypatch.setattr(wl, "staged", lambda *a, **k: calls.append(a))
    monkeypatch.setattr(wl, "listed_first", lambda *a: calls.append(a))
    assert text() == plain and not calls
