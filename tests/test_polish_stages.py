"""A polish wave runs a stage only when it has an input (PR 33).

``sliver_polish_impl`` skips its collapse stage when no live tet is under
``sliver_q`` and its exit ``build_adjacency`` when ``swap23`` applied no
swap.  Both are exact: a wave equals, leaf by leaf and to the bit, the
composition that runs every stage whatever its input (written here from
the public waves, as the function stood before), ``mesh.adja`` is the
returned mesh's own adjacency either way, and the counts row says which
of the two ran.

Meshes of one shape, two waves on each: a clean Kuhn cube (no tet under
the threshold, nothing to swap: both stages skipped), the same cube with
its interior vertices moved (a few slivers and a 2-3 swap: both run;
the second wave's ``swap23`` applies nothing and the exit adjacency is
kept) and that one under a tensor metric, two seeds (hundreds of tets
under the threshold in the metric, collapses applied, ``hausd`` vetoes).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.ops.adapt import sliver_polish, sliver_polish_impl
from parmmg_tpu.ops.adjacency import boundary_edge_tags, build_adjacency
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.collapse import collapse_wave
from parmmg_tpu.ops.smooth import smooth_wave
from parmmg_tpu.ops.swap import swap23_wave, swap_edges_wave
from parmmg_tpu.ops.swapgen import swapgen_wave
from parmmg_tpu.utils.fixtures import analytic_ani_metric, cube_mesh

N = 5                   # 750 tets
HAUSD = 0.01            # every cell of the benchmark runs with it
SLIVER_Q = 0.2
# case -> (jitter in cells, tensor metric?, (col, adj) wave by wave)
CASES = {
    "clean": (0.0, False, [(0, 0), (0, 0)]),
    "slivers": (0.3, False, [(1, 1), (1, 0)]),
    "tensor": (0.3, True, [(1, 1), (1, 1)]),
    "tensor-quiet-swap23": (0.3, True, [(1, 1), (1, 0)]),
}
SEEDS = {"tensor-quiet-swap23": 2}


@functools.cache
def _on_host(case):
    jitter, tensor, _ = CASES[case]
    vert, tet = cube_mesh(N)
    inner = ((vert > 1e-9) & (vert < 1 - 1e-9)).all(axis=1)
    vert = vert.copy()
    vert[inner] += np.random.default_rng(SEEDS.get(case, 4)).uniform(
        -jitter, jitter, (int(inner.sum()), 3)) / N
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    if tensor:
        h = analytic_ani_metric(vert, "shock")
        met = jnp.zeros((mesh.capP, 6), mesh.vert.dtype).at[
            :, jnp.array([0, 3, 5])].set(1.0)
        met = met.at[: len(h)].set(jnp.asarray(h, mesh.vert.dtype))
    else:
        met = jnp.full(mesh.capP, 1.0 / N, mesh.vert.dtype)
    return jax.tree.map(np.asarray, (mesh, met))


def fixture(case):
    """A fresh copy each call: ``sliver_polish`` donates its mesh."""
    return jax.tree.map(jnp.array, _on_host(case))


@jax.jit
def every_stage(mesh, met, wave):
    """The wave with every stage run whatever its input."""
    kw = dict(budget_div=2)
    col = collapse_wave(mesh, met, sliver_q=SLIVER_Q, hausd=HAUSD, **kw)
    mesh = jax.lax.cond(col.surface_changed, boundary_edge_tags,
                        lambda m: m, col.mesh)
    sew = swap_edges_wave(mesh, met, hausd=HAUSD, **kw)
    sgn = swapgen_wave(sew.mesh, met, **kw)
    s23 = swap23_wave(build_adjacency(sgn.mesh), met, **kw)
    sm = smooth_wave(s23.mesh, met, wave=wave, opt_q=SLIVER_Q, hausd=HAUSD)
    mesh = build_adjacency(sm.mesh)
    return mesh, jnp.stack([
        col.ncollapse, sew.nswap + sgn.nswap + s23.nswap, sm.nmoved,
        jnp.sum(mesh.tmask, dtype=jnp.int32), col.nhveto, sm.nbdy])


@functools.cache
def two_waves(case):
    """[(mesh, counts) of ``sliver_polish``, (mesh, counts) of the
    reference] for two consecutive waves, each chain on its own mesh."""
    out = []
    mesh, met = fixture(case)
    ref, _ = fixture(case)
    for w in (1000, 1001):
        wave = jnp.asarray(w, jnp.int32)
        mesh, counts = sliver_polish(mesh, met, wave, hausd=HAUSD)
        ref, ref_counts = every_stage(ref, met, wave)
        out.append(jax.tree.map(
            np.array, ((mesh, counts), (ref, ref_counts))))
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_wave_equals_the_wave_that_runs_every_stage(case):
    for (mesh, counts), (ref, ref_counts) in two_waves(case):
        for path, a in jax.tree_util.tree_leaves_with_path(mesh):
            b = dict(jax.tree_util.tree_leaves_with_path(ref))[path]
            assert a.dtype == b.dtype and np.array_equal(a, b), \
                f"{case}: leaf {jax.tree_util.keystr(path)} differs"
        assert counts[:6].tolist() == ref_counts.tolist()
    if case != "clean":
        assert sum(int(c[1]) for (_, c), _ in two_waves(case)) > 0
    if case.startswith("tensor"):
        # the collapse stage did work and its hausd test refused some
        first = two_waves(case)[0][0][1]
        assert first[0] > 0 and first[4] > 0


@pytest.mark.parametrize("case", list(CASES))
def test_exit_adjacency_is_the_returned_meshes_own(case):
    for (mesh, _), _ in two_waves(case):
        built = build_adjacency(jax.tree.map(jnp.asarray, mesh))
        assert np.array_equal(mesh.adja, np.asarray(built.adja))
        assert np.array_equal(mesh.ftag, np.asarray(built.ftag))


@pytest.mark.parametrize("case", list(CASES))
def test_counts_say_which_stage_ran(case):
    rows = [tuple(int(v) for v in c[7:9]) for (_, c), _ in two_waves(case)]
    assert rows == CASES[case][2]
    for (_, c), _ in two_waves(case):
        assert len(c) == 11 and c[9:].tolist() == [0, 0]  # no list: not counted
        assert int(c[7]) == int(c[6] > 0)   # collapse runs iff a tet is bad
        if not c[7]:
            assert c[0] == 0 and c[4] == 0


def test_with_the_retained_sorts_the_wave_is_still_that_wave():
    """PR 38: handed the sorts behind its tables (``ops/topo_incr``) and
    nothing else, the wave merges where it sorted, skips what it skipped
    and gives what the wave that runs every stage gives; the row's two
    new columns count the tables it derived."""
    from parmmg_tpu.ops.topo_incr import topo_init
    mesh, met = fixture("tensor")
    topo = topo_init(mesh.capT)
    for w, (_, (ref, ref_counts)) in enumerate(two_waves("tensor")):
        mesh, counts, topo = sliver_polish(
            mesh, met, jnp.asarray(1000 + w, jnp.int32), hausd=HAUSD,
            topo=topo)
        for a, b in zip(jax.tree.leaves(mesh), jax.tree.leaves(ref)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        counts = np.asarray(counts).tolist()
        assert counts[:6] == ref_counts.tolist()
        assert tuple(counts[7:9]) == CASES["tensor"][2][w]
        assert len(counts) == 13 and counts[9:11] == [0, 0]   # no list
        assert counts[11] == 5 and counts[12] == (3 if w == 0 else 5)


def test_without_swaps_the_exit_adjacency_is_still_built():
    """``do_swap=False``: no adjacency was built inside the wave, and the
    collapses changed the topology."""
    mesh, met = fixture("tensor")
    mesh, counts = sliver_polish(mesh, met, jnp.asarray(1000, jnp.int32),
                                 do_swap=False, hausd=HAUSD)
    counts = np.asarray(counts).tolist()
    assert counts[0] > 0 and counts[1] == 0
    assert counts[6] > 0 and counts[7:] == [1, 1, 0, 0]
    built = build_adjacency(mesh)
    assert np.array_equal(np.asarray(mesh.adja), np.asarray(built.adja))
    # and it is not the adjacency the wave was handed
    assert not np.array_equal(np.asarray(mesh.adja),
                              np.asarray(_on_host("tensor")[0].adja))


def test_a_stage_switched_off_leaves_no_cond_behind():
    """``do_collapse=False`` (``-noinsert``): the stage is off, not
    skipped, and the wave does not look for its input; with the swaps
    off too the exit build is unconditional."""
    mesh, met = fixture("slivers")
    shapes = jax.eval_shape(lambda m, k: sliver_polish_impl(
        m, k, jnp.asarray(0, jnp.int32), do_collapse=False), mesh, met)
    assert shapes[1].shape == (11,)
    jaxpr = jax.make_jaxpr(lambda m, k: sliver_polish_impl(
        m, k, jnp.asarray(0, jnp.int32), do_collapse=False,
        do_swap=False, do_smooth=False)[1])(mesh, met)
    assert "cond" not in str(jaxpr)         # the exit build, unconditional


def test_an_inactive_slot_hands_back_a_row_of_the_same_width():
    """The grouped polish's quiet mask: both branches of the ``active``
    cond give eleven columns (a mismatch would not trace)."""
    mesh, met = fixture("clean")
    shapes = jax.eval_shape(lambda m, k, act: sliver_polish_impl(
        m, k, jnp.asarray(0, jnp.int32), hausd=HAUSD, active=act),
        mesh, met, jnp.asarray(False))
    assert shapes[1].shape == (11,) and shapes[1].dtype == jnp.int32
