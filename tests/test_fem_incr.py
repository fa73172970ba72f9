"""A fem round merges its tables instead of sorting them again (PR 44).

``driver._merged_polish`` hands the ``ops/topo_incr.TopoState`` it ends
with to ``driver._finish_run``, whose fem rounds take their edge table
and their adjacency off its sorts (``ops/adapt.fem_pass_impl`` with a
state) and hand it from round to round; the result is the full sort's to
the bit:

(a) eight polish waves, then the repair and the fem rounds with the
    carried state against the same rounds with none: a scalar cube, a
    tensor cube, a ball under ``hausd``;
(b) the rounds' spans say how each table was made (``tab``, ``inc``) and
    ``tail.fem_tables`` / ``tail.fem_tables_merged`` are their sums, once
    a job, zeros too; a round from ``topo_init`` sorts both in full;
(c) a round that split nothing takes both tables as they are;
(d) a repair that rewrote rows is marked, and the round gives what the
    full sort gives; after a regrow the state is dropped and the next
    round sorts in full;
(e) without a state the program is the parent's, to the character.
"""
import dataclasses
import functools
import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from parmmg_tpu import driver
from parmmg_tpu.api.parmesh import ParMesh
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.obs.metrics import REGISTRY
from parmmg_tpu.ops import adapt, repair
from parmmg_tpu.ops import topo_incr as ti
from parmmg_tpu.ops import worklist as wl
from parmmg_tpu.ops.adjacency import boundary_edge_tags, build_adjacency
from parmmg_tpu.utils.timers import Timers

from test_merged_tail import padded
from test_polish_worklist import HAUSD, WAVES, fixture, same_mesh

CASES = ("cube", "cube-tensor", "ball")
COUNTERS = ("tail.fem_tables", "tail.fem_tables_merged")
# sha256 of ``fem_pass_impl``'s lowered text (no debug info) on
# ``fixture("cube")`` / ``fixture("cube-tensor")`` WITHOUT a state, as PR
# 45 left it (its sorts hand back their keys and payloads, the head table
# comes off a reverse scan: the text is not 8248ef4's, the round's result
# is, ``tests/test_rowpack.py``);
# ``PYTHONPATH=. python3 tests/test_fem_incr.py`` prints them anew
STATELESS_TEXT = {
    "cube":
        "6bddd60e66497ba6d830f69cf6fe6fb9b2f4e5a6bcabc56dc3c57989ae37ec1c",
    "cube-tensor":
        "8de804af140d679c205fb8620e81e995c3e4d05d99f85b117bdfda8ab6251256",
}


@functools.cache
def _polished(case):
    """The mesh, the metric and the state eight polish waves leave, as
    ``driver._merged_polish`` runs them (on the host)."""
    mesh, met = fixture(case)
    listed, topo = wl.all_dirty(mesh), ti.topo_init(mesh.capT)
    for w in range(WAVES):
        mesh, _, listed, topo = adapt.sliver_polish(
            mesh, met, jnp.asarray(1000 + w, jnp.int32), hausd=HAUSD,
            worklist=listed, topo=topo)
    return jax.tree.map(np.asarray, (mesh, met, topo))


def polished(case):
    """A fresh copy each call: a fem round donates its mesh."""
    return jax.tree.map(jnp.array, _polished(case))


def finish(mesh, met, topo, info=None):
    """``driver._finish_run`` on a mesh: (mesh, metric, the rounds'
    spans, what the two counters gained, stats)."""
    if info is None:
        info = ParMesh().info
    info.imprim = -1
    otrace.TRACER.configure(path=None)
    otrace.TRACER.reset()
    before = dict(REGISTRY.snapshot()["counters"])
    mesh, met, stats = driver._finish_run(
        None, mesh, met, adapt.AdaptStats(), info, Timers(), None, None,
        HAUSD, topo=topo)
    after = dict(REGISTRY.snapshot()["counters"])
    rounds = [r for r in otrace.TRACER.ring if r.get("name") == "fem round"]
    otrace.TRACER.reset()
    assert all(n in after for n in COUNTERS)            # zeros too
    gained = [after[n] - before.get(n, 0.0) for n in COUNTERS]
    return jax.tree.map(np.asarray, (mesh, met)), rounds, gained, stats


@functools.cache
def tails(case):
    """[with the polish's state carried, with none] through the tail."""
    out = []
    for carried in (True, False):
        mesh, met, topo = polished(case)
        out.append(finish(mesh, met, topo if carried else None))
    return out


def fresh_sorts(mesh):
    """(the mesh with its adjacency, a state that holds both its sorts
    and no dirty row): what one derivation of each table leaves."""
    band = ti.polish_bands(mesh.capT)
    _, topo, _ = ti.incr_unique_edges(mesh, ti.topo_init(mesh.capT),
                                      shell_slots=3, band=band)
    mesh, topo, _ = ti.incr_build_adjacency(mesh, topo, band=band)
    assert bool(topo.eok) and bool(topo.fok)
    assert not bool(jnp.any(topo.edirty) | jnp.any(topo.fdirty))
    return mesh, topo


def counts(rounds):
    return [[r[k] for k in ("split", "overflow", "bsplit")] for r in rounds]


# ---- (a) ------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES)
def test_the_state_gives_what_no_state_gives(case):
    ((mesh, met), rounds, _, stats), ((plain, pmet), prounds, _, pstats) = \
        tails(case)
    for path, a in jax.tree_util.tree_leaves_with_path(mesh):
        b = dict(jax.tree_util.tree_leaves_with_path(plain))[path]
        assert a.dtype == b.dtype and np.array_equal(a, b), \
            f"{case}: leaf {jax.tree_util.keystr(path)}"
    assert met.dtype == pmet.dtype and np.array_equal(met, pmet)
    assert counts(rounds) == counts(prounds)
    assert stats.nsplit == pstats.nsplit
    # the rounds did work (the ball's one round only verifies, as
    # sphere-growth's does), and ended on one that found nothing
    if case != "ball":
        assert len(rounds) >= 2 and rounds[0]["split"] > 0
    assert counts(rounds)[-1] == [0, 0, 0]


# ---- (b) ------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES)
def test_the_spans_say_how_each_table_was_made(case):
    (_, rounds, gained, _), (_, prounds, pgained, _) = tails(case)
    # every table of every round comes off what the polish left
    assert [(r["tab"], r["inc"]) for r in rounds] == [(2, 2)] * len(rounds)
    assert gained == [2 * len(rounds)] * 2
    # no state, no columns: the round is the parent's
    assert not any("tab" in r or "inc" in r for r in prounds)
    assert pgained == [0, 0]


def test_a_round_from_topo_init_sorts_both_in_full():
    mesh, met, _ = polished("cube")
    (out, _), rounds, gained, _ = finish(mesh, met, ti.topo_init(mesh.capT))
    assert [(r["tab"], r["inc"]) for r in rounds] == \
        [(2, 0)] + [(2, 2)] * (len(rounds) - 1)
    assert gained == [2 * len(rounds), 2 * len(rounds) - 2]
    assert same_mesh(out, tails("cube")[1][0][0])


def test_no_fem_no_tables_and_the_counters_say_zero():
    mesh, met, topo = polished("cube")
    info = ParMesh().info
    info.fem = False
    _, rounds, gained, _ = finish(mesh, met, topo, info)
    assert rounds == [] and gained == [0, 0]


# ---- (c) ------------------------------------------------------------------

def test_a_round_that_split_nothing_takes_its_tables_as_they_are():
    """The mesh the rounds converged on, through one more round: no
    candidate, no dirty row at either derivation (``_reuse``), and the
    sorts the state carries are not touched."""
    (mesh, met), _, _, _ = tails("cube")[0]
    mesh, met = jax.tree.map(jnp.array, (mesh, met))
    mesh, topo = fresh_sorts(mesh)
    kept = jax.tree.map(np.array, topo)
    want = jax.tree.map(np.array, mesh)
    out, _, fc, left = adapt.fem_pass(mesh, met, topo)
    assert np.asarray(fc).tolist() == [0, 0, 0, 2, 2]
    assert same_mesh(out, want)
    assert all(np.array_equal(a, b) for a, b in zip(left, kept))


# ---- (d) ------------------------------------------------------------------

def _fake_repair(killed):
    """A ``repair_mesh`` that kills ``killed`` live tets in numpy and
    rebuilds the tags as the real one does."""
    def fake(mesh, met, **kw):
        live = np.flatnonzero(np.asarray(mesh.tmask))
        dead = np.random.default_rng(7).choice(live, killed, replace=False)
        tmask = np.asarray(mesh.tmask).copy()
        tmask[dead] = False
        out = dataclasses.replace(mesh, tmask=jnp.asarray(tmask))
        return boundary_edge_tags(build_adjacency(out)), killed
    return fake


def test_a_repair_that_rewrote_rows_is_marked(monkeypatch):
    killed = 9
    monkeypatch.setattr(repair, "repair_mesh", _fake_repair(killed))
    handed = []
    real = adapt.fem_pass

    def spy(mesh, met, topo=None):
        handed.append(topo)
        return real(mesh, met) if topo is None else real(mesh, met, topo)
    monkeypatch.setattr(adapt, "fem_pass", spy)
    mesh, met, topo = polished("cube")
    pending = int(topo.fdirty.sum())
    (out, omet), rounds, _, _ = finish(mesh, met, topo)
    first = handed[0]
    assert int(first.fdirty.sum()) == pending + killed
    assert int(first.edirty.sum()) >= killed
    # a hole opens new boundary: the rounds have something to split
    assert rounds[0]["split"] > 0
    assert [(r["tab"], r["inc"]) for r in rounds] == [(2, 2)] * len(rounds)
    mesh, met, _ = polished("cube")
    (plain, pmet), prounds, _, _ = finish(mesh, met, None)
    assert same_mesh(out, plain) and np.array_equal(omet, pmet)
    assert counts(rounds) == counts(prounds)


def test_a_regrow_drops_the_state_and_the_next_round_sorts_in_full():
    """Eight free rows: the first round overflows, the loop regrows, the
    rows are permuted and the capacity doubled, so nothing of the sorts
    holds; the round after sorts in full and the rest merge again."""
    mesh, met = fixture("cube")
    n_p, n_t = mesh.np_counts()
    info = ParMesh().info
    info.noswap = info.nomove = True
    tight, tight_met = padded(mesh, met, n_p + 8, n_t + 8)
    _, topo = fresh_sorts(tight)
    (out, omet), rounds, gained, stats = finish(
        tight, tight_met, topo, info)
    tight, tight_met = padded(mesh, met, n_p + 8, n_t + 8)
    (plain, pmet), prounds, _, pstats = finish(
        tight, tight_met, None, info)
    assert stats.regrows == pstats.regrows >= 1
    assert same_mesh(out, plain) and np.array_equal(omet, pmet)
    assert counts(rounds) == counts(prounds)
    assert out.capT >= 2 * (n_t + 8)
    inc = [r["inc"] for r in rounds]
    for w, r in enumerate(rounds):
        assert r["tab"] == 2
        # the round after an overflow meets a fresh state
        assert inc[w] == (0 if w and rounds[w - 1]["overflow"] else 2)
    assert gained == [2 * len(rounds), sum(inc)]
    assert rounds[-1]["split"] == 0 and not rounds[-1]["overflow"]


# ---- (e) ------------------------------------------------------------------

def _text(case):
    mesh, met = fixture(case)
    return hashlib.sha256(adapt.fem_pass.__wrapped__.lower(
        mesh, met).as_text().encode()).hexdigest()


@pytest.mark.parametrize("case", sorted(STATELESS_TEXT))
def test_without_a_state_the_program_is_the_pinned_text(case):
    assert _text(case) == STATELESS_TEXT[case]


def test_with_a_state_the_stages_keep_their_scopes():
    mesh, met = fixture("cube")
    debug = adapt.fem_pass.__wrapped__.lower(
        mesh, met, ti.topo_init(mesh.capT)).as_text(debug_info=True)
    for name in ("fem.split", "fem.bdytags", "fem.adjacency", "tab.edges",
                 "tab.adjacency"):
        assert name in debug


if __name__ == "__main__":
    for case in sorted(STATELESS_TEXT):
        print(case, _text(case))
