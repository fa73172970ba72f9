"""A polish wave merges its tables instead of sorting them again (PR 38).

The merged polish hands its waves, beside the worklist, the edge and face
sorts behind their tables (an ``ops/topo_incr.TopoState``): every table a
wave derives (the collapse stage's edge table, the edge swaps' and the
ring swaps', ``swap23``'s adjacency, the exit adjacency) is the retained
sort with the rows the stages dirtied since merged in, and the result is
the full sort's to the bit:

(a) eight waves with the state and the list, as the driver runs them,
    against eight of the wave that takes neither: a scalar cube, a tensor
    cube, a ball under ``hausd``;
(b) the counts row says how each table was made: wave 0 sorts its first
    edge table and its first adjacency in full (nothing is retained yet)
    and merges the rest, a later wave merges every one, a wave that
    changed nothing takes them as they are, a wave whose dirty rows
    outnumber the widest band sorts in full and gives the same;
(c) bands of several rungs merge at the narrowest that holds the rows;
(d) ``tail.tables`` / ``tail.tables_merged`` are the waves' sums, once a
    job, zeros too; the host-placed polish holds no Pallas call.
"""
import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from parmmg_tpu.ops import topo_incr as ti
from parmmg_tpu.ops import worklist as wl
from parmmg_tpu.ops.adapt import sliver_polish, sliver_polish_impl
from parmmg_tpu.ops.adjacency import build_adjacency
from parmmg_tpu.ops.edges import unique_edges

from test_polish_worklist import (HAUSD, WAVES, _clean_cube, fixture,
                                  same_mesh)

CASES = ("cube", "cube-tensor", "ball")
TAB, INC = 11, 12       # the two columns a state adds to the counts row


def wave_id(w):
    return jnp.asarray(1000 + w, jnp.int32)


@functools.cache
def eight_waves(case):
    """Per wave [(mesh, counts, state) with the state and the list
    carried, (mesh, counts) of the wave that takes neither], each chain
    on its own mesh."""
    mesh, met = fixture(case)
    plain, _ = fixture(case)
    listed, topo = wl.all_dirty(mesh), ti.topo_init(mesh.capT)
    out = []
    for w in range(WAVES):
        mesh, counts, listed, topo = sliver_polish(
            mesh, met, wave_id(w), hausd=HAUSD, worklist=listed, topo=topo)
        plain, pcounts = sliver_polish(plain, met, wave_id(w), hausd=HAUSD)
        out.append(jax.tree.map(np.array, ((mesh, counts, topo),
                                           (plain, pcounts))))
    return out


# ---- (a) ------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES)
def test_the_state_gives_what_no_state_gives(case):
    for w, ((mesh, counts, _), (plain, pcounts)) in enumerate(
            eight_waves(case)):
        for path, a in jax.tree_util.tree_leaves_with_path(mesh):
            b = dict(jax.tree_util.tree_leaves_with_path(plain))[path]
            assert a.dtype == b.dtype and np.array_equal(a, b), \
                f"{case} wave {w}: leaf {jax.tree_util.keystr(path)}"
        assert counts[:9].tolist() == pcounts[:9].tolist()
    # the waves did work
    total = np.sum([c for (_, c, _), _ in eight_waves(case)], axis=0)
    assert total[1] > 20 and total[2] > 20
    if case.endswith("tensor"):
        assert total[0] > 0 and total[4] > 0    # collapses, hausd vetoes


# ---- (b) ------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES)
def test_the_counts_say_how_each_table_was_made(case):
    for w, ((_, counts, topo), (_, pcounts)) in enumerate(
            eight_waves(case)):
        assert len(counts) == 13 and len(pcounts) == 11
        col, adj = int(counts[7]), int(counts[8])
        # the collapse stage's table if it ran, the two swap kernels',
        # swap23's adjacency, the exit adjacency if swap23 applied
        assert int(counts[TAB]) == col + 3 + adj
        if w == 0:
            # nothing retained: the first edge table and the first
            # adjacency are sorted in full, the rest merged into them
            assert int(counts[INC]) == int(counts[TAB]) - 2
        else:
            assert int(counts[INC]) == int(counts[TAB])
        assert bool(topo.eok) and bool(topo.fok)
        # the wave's last adjacency met everything swap23 changed;
        # the ring swaps' and swap23's rows wait for the next edge table
        assert not topo.fdirty.any()
        assert int(counts[1]) > 0 or not topo.edirty.any()


def test_a_wave_over_the_widest_band_sorts_in_full_and_gives_the_same():
    """Every row marked dirty (over-marking is exact, and more than any
    band holds): wave 3's first edge table and first adjacency take the
    full sort, and the wave gives what the carried state gave."""
    rows = eight_waves("cube-tensor")
    (before, _, topo), _ = rows[2]
    (after, counts, _), _ = rows[3]
    _, met = fixture("cube-tensor")
    mesh = jax.tree.map(jnp.array, before)
    assert max(ti.polish_bands(mesh.capT)) < mesh.capT
    over = jax.tree.map(jnp.asarray, topo)._replace(
        edirty=jnp.ones(mesh.capT, bool), fdirty=jnp.ones(mesh.capT, bool))
    mesh, ocounts, _, left = sliver_polish(
        mesh, met, wave_id(3), hausd=HAUSD, worklist=wl.all_dirty(mesh),
        topo=over)
    assert same_mesh(mesh, after)
    ocounts = np.asarray(ocounts)
    assert ocounts[:9].tolist() == counts[:9].tolist()
    assert int(ocounts[TAB]) == int(counts[TAB]) == int(counts[INC])
    assert int(ocounts[INC]) == int(ocounts[TAB]) - 2
    # and the sorts it retains are the carried chain's
    kept = rows[3][0][2]
    assert all(np.array_equal(a, b) for a, b in zip(left, kept))


def test_a_wave_that_changed_nothing_takes_its_tables_as_they_are():
    mesh, met = _clean_cube()
    listed, topo = wl.all_dirty(mesh), ti.topo_init(mesh.capT)
    for w in range(2):
        mesh, counts, listed, topo = sliver_polish(
            mesh, met, wave_id(w), hausd=HAUSD, worklist=listed, topo=topo)
        counts = np.asarray(counts).tolist()
        assert counts[:3] == [0, 0, 0] and counts[7:9] == [0, 0]
        # no collapse stage, no exit adjacency: two edge tables and one
        # adjacency; in wave 0 the ring swaps' table is the edge swaps'
        assert counts[TAB] == 3
        assert counts[INC] == (1 if w == 0 else 3)
        assert not bool(jnp.any(topo.edirty) | jnp.any(topo.fdirty))


# ---- (c) ------------------------------------------------------------------

BANDS = (8, 64)


@functools.cache
def _tables():
    def both(mesh, topo):
        et, topo, emerged = ti.incr_unique_edges(
            mesh, topo, shell_slots=3, band=BANDS)
        mesh, topo, fmerged = ti.incr_build_adjacency(
            mesh, topo, band=BANDS)
        return et, mesh, topo, emerged, fmerged
    return jax.jit(both), jax.jit(
        lambda m: (unique_edges(m), build_adjacency(m)))


@pytest.mark.parametrize("killed, merged", [
    (0, True), (5, True), (8, True), (9, True), (64, True), (65, False),
    (300, False)])
def test_bands_of_two_rungs_merge_at_the_narrowest_that_holds(killed,
                                                              merged):
    """``killed`` tets die between two derivations: none (the retained
    sort as it is), up to 8 (the narrow rung), up to 64 (the wide one:
    the narrow one would cut the band short), more (the full sort)."""
    derive, full = _tables()
    mesh, _ = fixture("cube")
    _, mesh, topo, first, _ = derive(mesh, ti.topo_init(mesh.capT))
    assert not bool(first)                      # nothing retained yet
    live = np.flatnonzero(np.asarray(mesh.tmask))
    dead = np.random.default_rng(killed).choice(live, killed, replace=False)
    after = dataclasses.replace(
        mesh, tmask=mesh.tmask.at[jnp.asarray(dead, jnp.int32)].set(False))
    topo = ti.mark_dirty(topo, mesh.tet, mesh.tmask, after)
    assert int(topo.edirty.sum()) == int(topo.fdirty.sum()) == killed
    et, adj, topo, emerged, fmerged = derive(after, topo)
    assert bool(emerged) == bool(fmerged) == merged
    want_et, want_adj = full(after)
    assert all(np.array_equal(a, b) for a, b in zip(et, want_et))
    assert same_mesh(adj, want_adj)
    assert not bool(jnp.any(topo.edirty) | jnp.any(topo.fdirty))


def test_the_polish_asks_for_bands_of_its_capacity_alone():
    assert ti.polish_bands(47895) == (2993, 11973)  # iso-growth's merged
    assert ti.polish_bands(18540) == (1158, 4635)   # aniso-coarsen's
    assert ti.polish_bands(2250) == (1024,)         # a toy: one rung
    assert ti.polish_bands(600) == (600,)


# ---- (d) ------------------------------------------------------------------

def _stub_rows(rows):
    """A ``sliver_polish`` that hands back ``rows`` as its counts, one a
    wave, and everything else as it got it."""
    left = list(rows)

    def stub(mesh, met, wave, worklist=None, topo=None, **kw):
        return mesh, jnp.asarray(left.pop(0), jnp.int32), worklist, topo
    return stub


@pytest.mark.parametrize("rows, tables, merged", [
    ([[0] * 13], 0, 0),
    ([[0, 7] + [0] * 9 + [5, 3], [0] * 11 + [3, 3]], 8, 6)])
def test_tail_tables_are_published_once_a_job_zeros_too(
        monkeypatch, rows, tables, merged):
    from parmmg_tpu import driver
    from parmmg_tpu.api.parmesh import ParMesh
    from parmmg_tpu.obs import trace as otrace
    from parmmg_tpu.obs.metrics import REGISTRY
    from parmmg_tpu.ops import adapt
    from parmmg_tpu.utils.timers import Timers
    monkeypatch.setattr(adapt, "sliver_polish", _stub_rows(rows))
    names = ("tail.tables", "tail.tables_merged")
    before = dict(REGISTRY.snapshot()["counters"])
    otrace.TRACER.configure(path=None)
    otrace.TRACER.reset()
    mesh, met = _clean_cube()
    info = ParMesh().info
    info.imprim = -1
    driver._merged_polish(mesh, met, info, None, adapt.AdaptStats(),
                          Timers())
    after = dict(REGISTRY.snapshot()["counters"])
    assert all(n in after for n in names)           # zeros too
    assert [after[n] - before.get(n, 0.0) for n in names] == \
        [tables, merged]
    waves = [r for r in otrace.TRACER.ring if r.get("name") == "polish wave"]
    otrace.TRACER.reset()
    assert [(r["tab"], r["inc"]) for r in waves] == \
        [(r[TAB], r[INC]) for r in rows]


def test_the_host_placed_polish_holds_no_pallas_call():
    """The tail is placed on XLA:CPU (``host_staging``) in a process
    whose default backend is a TPU: the merge's prefix sums must lower
    to ``cumsum`` there, not to the Pallas kernel they are on a chip.
    The choice is made at lowering time, by platform."""
    from parmmg_tpu.ops import pallas_kernels as pk
    from parmmg_tpu.utils.placement import host_staging
    mesh, met = fixture("cube")
    with host_staging():
        traced = jax.jit(functools.partial(
            sliver_polish_impl, hausd=HAUSD)).trace(
                mesh, met, wave_id(0), worklist=wl.all_dirty(mesh),
                topo=ti.topo_init(mesh.capT))
        text = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in text
    assert "stablehlo.case" in text
    if pk.use_pallas():
        # and the same merge, lowered for a chip, does hold it
        band = jax.jit(lambda x: ti._prefix_i32(x)).trace(
            jnp.zeros(4096, jnp.int32))
        assert "tpu_custom_call" in band.lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" not in band.lower(
            lowering_platforms=("cpu",)).as_text()
