"""A cycle block scatters only its surface (PR 40, ``ops/surflist``).

The four surface scatters of a cycle (vertex normals, ridge tangents,
boundary tags, the smoother's surface sums, and the second form beside
them) run over a list of their live updates where the program is placed
on a TPU, at full width elsewhere.  Here on the CPU the list is forced
through the static argument the sites take (``lists=Tally(True)``) and
held to the full-width scatter to the bit: the primitive on masks round a
chunk's edges, every site on a cube, a sphere and a torus, a grouped
two-pass job, and the placement rule that keeps the host's programs what
they were.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parmmg_tpu.core.constants import MG_BDY
from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.obs.metrics import REGISTRY
from parmmg_tpu.ops import adapt, surflist
from parmmg_tpu.ops.adjacency import boundary_edge_tags
from parmmg_tpu.ops.analysis import (analyze_mesh, boundary_second_form,
                                     boundary_vertex_normals,
                                     ridge_vertex_tangents)
from parmmg_tpu.ops.smooth import smooth_wave
from parmmg_tpu.parallel import groups
from parmmg_tpu.utils import placement
from parmmg_tpu.utils.fixtures import cube_mesh, sphere_mesh, torus_mesh
from test_block_one_program import digest

HAUSD = 0.01
ROWS, N = 40, 1000
CHUNK = -(-N // surflist.CHUNK_DIV)


def bits(tree):
    """Leaves as raw bytes: equal means equal to the bit, NaNs too."""
    return [np.asarray(a).tobytes() for a in jax.tree.leaves(tree)]


# ---- the primitive ----------------------------------------------------------

@pytest.mark.parametrize("op", ["add", "max"])
@pytest.mark.parametrize("live", [0, CHUNK - 1, CHUNK, CHUNK + 1, N],
                         ids=["empty", "chunk-1", "chunk", "chunk+1", "all"])
def test_the_staged_scatter_is_the_full_width_scatter(live, op):
    """``live`` of N updates survive, many to a row (40 rows), at random
    positions: the list's sum, added in ascending position a chunk at a
    time, is the concatenated scatter's to the bit; 0, one chunk less
    one, one chunk, one more, and all of them."""
    rng = np.random.default_rng(live)
    mask = np.zeros(N, bool)
    mask[rng.choice(N, live, replace=False)] = True
    rows = rng.integers(0, ROWS, N).astype(np.int32)
    pay = (rng.standard_normal((N, 3)) * 10.0 ** rng.integers(
        -3, 4, (N, 1))).astype(np.float32)

    def full(mask, rows, pay):
        at = jnp.zeros((ROWS + 1, 3), jnp.float32).at[
            jnp.where(mask, rows, ROWS)]
        return (at.add(pay, mode="drop") if op == "add"
                else at.max(pay, mode="drop"))[:ROWS]

    def listed(mask, rows, pay):
        lst = surflist.Live(mask)
        assert lst.chunk == CHUNK and lst.pos.shape == (N + CHUNK,)
        out = surflist.staged_scatter(
            jnp.zeros((ROWS + 1, 3), jnp.float32), lst,
            lambda p, ok: (jnp.where(ok, rows[p], ROWS), pay[p]), op=op)
        return out[:ROWS], lst.count

    want = jax.jit(full)(mask, rows, pay)
    got, count = jax.jit(listed)(mask, rows, pay)
    assert int(count) == live
    assert bits(got) == bits(want)


def test_take_and_table_rows_are_the_gathers():
    rng = np.random.default_rng(3)
    rows = rng.standard_normal((50, 4, 3)).astype(np.float32)
    col = rng.integers(0, 4, 50).astype(np.int32)
    assert np.array_equal(surflist.take(jnp.asarray(rows), col),
                          rows[np.arange(50), col])
    from parmmg_tpu.core.constants import IDIR
    assert np.array_equal(surflist.table_rows(IDIR, col), IDIR[col])
    tets = rng.integers(0, 99, (50, 4)).astype(np.int32)
    assert np.array_equal(surflist.face_vertices(jnp.asarray(tets), col),
                          np.take_along_axis(tets, IDIR[col], axis=1))


# ---- the four sites, and the second form ------------------------------------

def shuffled(vert, tet, seed=5, jitter=0.0):
    """The fixture with its tet rows shuffled (a list in row order is
    then no list in space) at ``make_mesh``'s 3x, analysed."""
    rng = np.random.default_rng(seed)
    tet = tet[rng.permutation(len(tet))]
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    if jitter:
        # interior vertices off their lattice, so that smoothing moves
        interior = np.asarray(mesh.vtag)[: len(vert)] == 0
        vert = vert + jitter * interior[:, None] * rng.uniform(
            -1, 1, vert.shape)
        mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    return mesh


@pytest.fixture(scope="module")
def meshes():
    return {"cube": shuffled(*cube_mesh(5), jitter=0.03),
            "sphere": shuffled(*sphere_mesh(6)),
            "torus": shuffled(*torus_mesh(16, 4))}


def site(name, on):
    """The jitted site with the list forced ``on`` or off; returns the
    result's leaves and the updates its lists held."""
    def run(mesh):
        lists = surflist.Tally(on)
        if name == "normals":
            out = boundary_vertex_normals(mesh, lists=lists)
        elif name == "tangents":
            out = ridge_vertex_tangents(mesh, lists=lists)
        elif name == "bdytags":
            out = boundary_edge_tags(mesh, lists=lists)
        elif name == "second_form":
            out = boundary_second_form(
                mesh, boundary_vertex_normals(mesh, lists=lists),
                lists=lists)
        else:
            met = jnp.full(mesh.capP, 0.25, mesh.vert.dtype)
            res = smooth_wave(mesh, met, wave=1, hausd=HAUSD, lists=lists)
            out = (res.mesh.vert, res.nmoved, res.nbdy)
        return out, jnp.asarray(lists.listed, jnp.int32)
    return jax.jit(run)


@pytest.mark.parametrize("name", ["normals", "tangents", "bdytags",
                                  "smooth", "second_form"])
@pytest.mark.parametrize("fixture", ["cube", "sphere", "torus"])
def test_a_site_over_its_list_is_the_site_at_full_width(meshes, fixture,
                                                        name):
    mesh = meshes[fixture]
    got, listed = site(name, True)(mesh)
    want, none = site(name, False)(mesh)
    assert bits(got) == bits(want)
    assert int(none) == 0
    capT = mesh.capT
    faces = int(np.sum((np.asarray(mesh.ftag) & MG_BDY != 0)
                       & np.asarray(mesh.tmask)[:, None]))
    if name == "tangents":
        # a cube has ridges; a sphere and a torus none
        assert (int(listed) > 0) == (fixture == "cube")
    else:
        assert 0 < int(listed) <= 16 * faces < 12 * capT
    if name == "smooth" and fixture != "cube":
        # the curved arm ran: the second form's list is counted too
        assert int(listed) > 3 * faces


def test_smoothing_moves_something_on_every_fixture(meshes):
    """The comparison above is of moved meshes, not of two identities."""
    for mesh in meshes.values():
        vert, nmoved, nbdy = site("smooth", True)(mesh)[0]
        assert int(nmoved) > 0
        assert not np.array_equal(np.asarray(vert), np.asarray(mesh.vert))


def test_a_cycle_counts_its_lists_in_column_seven(meshes):
    """One cycle with the lists on: the mesh and the counts row are the
    full-width cycle's but for ``LISTED_COL``, which holds what the
    lists held, a share of ``surface_scatter_width``."""
    mesh = meshes["sphere"]
    met = jnp.full(mesh.capP, 0.2, mesh.vert.dtype)
    wave = jnp.asarray(2, jnp.int32)
    out = {}
    for on in (True, False):
        m, k = jax.tree.map(jnp.copy, (mesh, met))
        out[on] = adapt.adapt_cycle(m, k, wave, hausd=HAUSD, surf_list=on)
    (m1, k1, c1), (m0, k0, c0) = out[True], out[False]
    assert bits((m1, k1)) == bits((m0, k0))
    c1, c0 = np.array(c1), np.array(c0)
    assert c0[adapt.LISTED_COL] == 0
    full = adapt.surface_scatter_width(mesh.capT, hausd=HAUSD)
    assert full == 52 * mesh.capT
    assert 0 < c1[adapt.LISTED_COL] < 0.2 * full
    c1[adapt.LISTED_COL] = 0
    assert np.array_equal(c1, c0)
    assert c0[:4].sum() > 0


# ---- a grouped two-pass job -------------------------------------------------

@pytest.fixture(scope="module")
def jobs():
    """``sphere_mesh(4)`` (384 tets) in two groups, two passes of three
    cycles under ``hausd``, once as the CPU builds the block (full
    width) and once with the block built as on a TPU (lists on)."""
    mp = pytest.MonkeyPatch()
    out = {}
    vert, tet = sphere_mesh(4)
    try:
        for on in (False, True):
            mp.setattr(groups, "placed_on_tpu", lambda on=on: on)
            otrace.TRACER.configure(path=None)
            otrace.TRACER.reset()
            mesh = analyze_mesh(make_mesh(vert, tet)).mesh
            met = jnp.full(mesh.capP, 0.3, mesh.vert.dtype)
            stats = adapt.AdaptStats()
            mesh, met = groups.grouped_adapt(
                mesh, met, target_size=len(tet) // 2, niter=2, cycles=3,
                hausd=HAUSD, stats=stats)
            blocks = [r for r in otrace.TRACER.ring
                      if r.get("name") == "grp block"]
            out[on] = {"digest": digest(mesh, met), "stats": stats,
                       "blocks": blocks}
    finally:
        mp.undo()
        otrace.TRACER.reset()
    return out


def test_the_grouped_job_is_the_full_width_job(jobs):
    assert jobs[True]["digest"] == jobs[False]["digest"]
    assert jobs[True]["digest"]["ntets"] > 384


def test_the_grouped_jobs_counters_differ_by_the_lists_alone(jobs):
    on, off = jobs[True]["stats"], jobs[False]["stats"]
    for name in ("nsplit", "ncollapse", "nswap", "nmoved", "cycles",
                 "nbsplit", "nhveto", "nbmoved"):
        assert getattr(on, name) == getattr(off, name), name
    assert on.nsplit > 0 and on.nbmoved > 0
    assert (off.nlisted, off.nlist_full) == (0, 0)
    assert 0 < on.nlisted < 0.2 * on.nlist_full
    keys = ("split", "collapse", "swap", "moved", "bsplit", "hveto",
            "bmoved")
    assert [[r[k] for k in keys] for r in jobs[True]["blocks"]] == \
        [[r[k] for k in keys] for r in jobs[False]["blocks"]]
    # three cycles a pass, and one more where a pass regrew
    assert len(jobs[True]["blocks"]) >= 6
    assert all(r["listed"] > 0 for r in jobs[True]["blocks"])
    assert all(r["listed"] == 0 for r in jobs[False]["blocks"])
    assert sum(r["listed"] for r in jobs[True]["blocks"]) == on.nlisted


def test_the_lists_counters_are_published(jobs):
    reg = type(REGISTRY)()
    jobs[True]["stats"].publish(reg)
    snap = reg.snapshot()["counters"]
    assert snap["surf.listed"] == jobs[True]["stats"].nlisted
    assert snap["surf.list_full"] == jobs[True]["stats"].nlist_full
    reg = type(REGISTRY)()
    jobs[False]["stats"].publish(reg)
    snap = reg.snapshot()["counters"]
    assert snap["surf.listed"] == 0 and snap["surf.list_full"] == 0


# ---- placement: observed, not set -------------------------------------------

def test_the_tally_follows_the_placement_helper(monkeypatch):
    """On where the program being traced is placed on a TPU, off on the
    CPU and for whatever a TPU process stages on its host; a caller's
    word stands over both."""
    assert surflist.Tally().on is (jax.default_backend() == "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert placement.placed_on_tpu() is True
    assert surflist.Tally().on is True
    with placement.host_staging():
        assert placement.placed_on_tpu() is False
        assert surflist.Tally().on is False
        assert surflist.Tally(True).on is True
    assert surflist.Tally(False).on is False


def test_facesort_asks_the_same_helper(monkeypatch):
    """``swap_facesort_enabled`` keeps its three answers (unset on a TPU
    placement, unset on the CPU, forced) through the shared helper."""
    from parmmg_tpu.ops.swap import swap_facesort_enabled
    monkeypatch.delenv("PARMMG_SWAP_FACESORT", raising=False)
    for placed in (True, False):
        monkeypatch.setattr(placement, "placed_on_tpu", lambda p=placed: p)
        assert swap_facesort_enabled() is placed
        assert surflist.Tally().on is placed
    monkeypatch.setattr(placement, "placed_on_tpu", lambda: False)
    monkeypatch.setenv("PARMMG_SWAP_FACESORT", "1")
    assert swap_facesort_enabled() is True
    monkeypatch.setattr(placement, "placed_on_tpu", lambda: True)
    monkeypatch.setenv("PARMMG_SWAP_FACESORT", "0")
    assert swap_facesort_enabled() is False


# the merged mesh of ``iso-growth`` at CPU seed 21 (capP, capT), the shape
# its polish and fem programs are lowered for
ISO_MERGED = (9244, 47895)


@pytest.fixture(scope="module")
def host_programs():
    """The lowered text (no debug info) of the merged polish and of a fem
    round at ``iso-growth``'s merged shape, as the driver calls them, on
    three code paths: this process (the CPU), a TPU process that stages
    them on its host, and a program placed on a TPU."""
    from parmmg_tpu.driver import polish_budget
    from parmmg_tpu.ops.topo_incr import topo_init
    from parmmg_tpu.ops.worklist import all_dirty
    capP, capT = ISO_MERGED
    small = make_mesh(*cube_mesh(2))

    def at(a):
        shape = tuple({small.capP: capP, small.capT: capT}.get(d, d)
                      for d in a.shape)
        return jax.ShapeDtypeStruct(shape, a.dtype)
    mesh = jax.tree.map(at, small)
    met = jax.ShapeDtypeStruct((capP,), jnp.float32)
    wave = jax.ShapeDtypeStruct((), jnp.int32)
    wl = jax.eval_shape(lambda: all_dirty(make_mesh(
        *cube_mesh(2), capP=capP, capT=capT)))
    topo = jax.eval_shape(lambda: topo_init(capT))

    def lowered():
        # fresh functions: jit keeps a traced program by the function's
        # identity, and where a program is placed is read while tracing
        def polish(mesh, met, wave, wl, topo):
            return adapt.sliver_polish_impl(
                mesh, met, wave, hausd=HAUSD, budget=polish_budget(31930),
                worklist=wl, topo=topo)

        def fem(mesh, met):
            return adapt.fem_pass_impl(mesh, met)
        return {"polish": jax.jit(polish).lower(
                    mesh, met, wave, wl, topo).as_text(),
                "fem": jax.jit(fem).lower(mesh, met).as_text()}

    mp = pytest.MonkeyPatch()
    out = {}
    try:
        mp.delenv("PARMMG_SWAP_FACESORT", raising=False)
        out["cpu"] = lowered()
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with placement.host_staging():
            out["staged"] = lowered()
        # the face sort is the same helper's other reader: held off, so
        # that what differs is the lists alone
        mp.setenv("PARMMG_SWAP_FACESORT", "0")
        out["tpu"] = lowered()
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("program", ["polish", "fem"])
def test_a_host_placed_program_is_what_it_was(host_programs, program):
    """Staged on the host by a process that holds a chip, the program's
    lowered text is this CPU process's, scatter for scatter: the lists
    are no part of it.  Placed on a TPU it holds them (a sort a list)."""
    cpu, staged, tpu = (host_programs[k][program]
                        for k in ("cpu", "staged", "tpu"))
    assert hashlib.sha256(staged.encode()).hexdigest() == \
        hashlib.sha256(cpu.encode()).hexdigest()
    assert tpu != cpu
    sorts = [t.count("stablehlo.sort") for t in (cpu, tpu)]
    assert sorts[1] > sorts[0]
