"""The merged tail runs at the width of its content (PR 29).

``merge_shards`` gives a merged mesh 1.5x its live rows, not
``make_mesh``'s 3x for a mesh that grows in place, and the polish's
candidate budget is stated in rows of content (``driver.polish_budget``)
so it does not shrink with the padding.  The gates: the capacity rule,
exactness against the old layout (3x, the wide divisor), the budget
every wave gets, and the fem loop's regrow at an exhausted capacity.

The fixture is the merged mesh of a two-group split of a jittered cube
(slivers for the polish, boundary-to-boundary interior edges for fem),
large enough that the budget is over ``wave_budget``'s floor of 2048.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from parmmg_tpu import driver
from parmmg_tpu.api.parmesh import ParMesh
from parmmg_tpu.core.mesh import make_mesh, with_capacity
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.ops.adapt import AdaptStats
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.parallel.distribute import merge_shards, split_to_shards
from parmmg_tpu.parallel.partition import fix_contiguity, morton_partition
from parmmg_tpu.utils.fixtures import (analytic_ani_metric,
                                       analytic_iso_metric, cube_mesh)
from parmmg_tpu.utils.timers import Timers

N = 7           # 2,058 tets: (3 * 2058) // 2 = 3,087 > 2,048


@functools.cache
def _merged_on_host(kind):
    vert, tet = cube_mesh(N)
    inner = ((vert > 1e-9) & (vert < 1 - 1e-9)).all(axis=1)
    vert = vert.copy()
    vert[inner] += np.random.default_rng(5).uniform(
        -0.3, 0.3, (int(inner.sum()), 3)) / N
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    if kind == "iso":
        h = analytic_iso_metric(vert, "shock", h=1.0 / N)
        met = jnp.ones(mesh.capP, mesh.vert.dtype)
    else:
        h = analytic_ani_metric(vert, "shock")
        met = jnp.zeros((mesh.capP, 6), mesh.vert.dtype).at[
            :, jnp.array([0, 3, 5])].set(1.0)
    met = met.at[: len(h)].set(jnp.asarray(h, mesh.vert.dtype))
    part = fix_contiguity(tet, morton_partition(vert[tet].mean(axis=1), 2))
    stacked, met_s = split_to_shards(mesh, met, part, 2)
    return jax.tree.map(np.asarray, merge_shards(stacked, met_s))


def merged_fixture(kind):
    """(merged mesh, metric) as ``merge_shards`` returns them; a fresh
    copy each call, because the tail's programs donate their input."""
    return jax.tree.map(jnp.array, _merged_on_host(kind))


def padded(mesh, met, capP, capT):
    """The same rows in a mesh of another capacity."""
    out = with_capacity(mesh, capP, capT)
    full = np.zeros((capP,) + met.shape[1:], np.asarray(met).dtype)
    keep = min(capP, mesh.capP)
    full[:keep] = np.asarray(met)[:keep]
    return out, jnp.asarray(full)


def run_tail(mesh, met):
    """The driver's own tail: ``_merged_polish``, then the repair and
    the fem rounds of ``_finish_run``.  Returns the mesh, the per-wave
    [collapse, swap, moved] and the fem rounds' [split, overflow]."""
    info = ParMesh().info
    info.imprim = -1
    stats, tim = AdaptStats(), Timers()
    otrace.TRACER.configure(path=None)
    otrace.TRACER.reset()
    mesh, _, topo = driver._merged_polish(mesh, met, info, None, stats,
                                          tim)
    mesh, met, stats = driver._finish_run(None, mesh, met, stats, info,
                                          tim, None, None, None, topo=topo)
    recs = [r for r in otrace.TRACER.ring if r.get("kind") == "span"]
    waves = [[r["collapse"], r["swap"], r["moved"]] for r in recs
             if r["name"] == "polish wave"]
    fem = [[r["split"], r["overflow"]] for r in recs
           if r["name"] == "fem round"]
    otrace.TRACER.reset()
    return mesh, waves, fem, stats


def live(mesh):
    tm, vm = np.asarray(mesh.tmask), np.asarray(mesh.vmask)
    return np.asarray(mesh.tet)[tm], np.asarray(mesh.vert)[vm]


@pytest.mark.parametrize("kind", ["iso", "tensor"])
def test_merged_mesh_holds_one_and_a_half_times_its_content(kind):
    mesh, met = merged_fixture(kind)
    tm, vm = np.asarray(mesh.tmask), np.asarray(mesh.vmask)
    n_p, n_t = mesh.np_counts()
    assert n_t <= mesh.capT <= (3 * n_t) // 2 + 64
    assert n_p <= mesh.capP <= (3 * n_p) // 2 + 64
    assert met.shape[0] == mesh.capP
    # compact: live rows first, so the free rows are n_t, n_t + 1, ...
    assert tm[:n_t].all() and vm[:n_p].all()
    # the polish's budget fits the arrays it reads
    assert driver.polish_budget(n_t) <= max(2048, mesh.capT)


@pytest.mark.parametrize("kind", ["iso", "tensor"])
def test_tail_is_exact_against_the_old_width_and_budget(kind, monkeypatch):
    """The same operations on the same rows in the same order: the mesh
    as ``merge_shards`` returns it against the old layout, padded to 3x
    with the wide divisor on the padding (``budget=None``)."""
    mesh, met = merged_fixture(kind)
    n_p, n_t = mesh.np_counts()
    old_mesh, old_met = padded(mesh, met, 3 * n_p, 3 * n_t)
    new, waves, fem, stats = run_tail(mesh, met)
    monkeypatch.setattr(driver, "polish_budget", lambda n_live: None)
    old, old_waves, old_fem, old_stats = run_tail(old_mesh, old_met)
    assert len(waves) == 8 and sum(w[1] for w in waves) > 0
    assert sum(f[0] for f in fem) > 0
    if kind == "tensor":
        assert sum(w[0] for w in waves) > 0         # collapses too
    assert waves == old_waves
    assert fem == old_fem and not any(f[1] for f in fem)
    assert stats.regrows == old_stats.regrows == 0
    for a, b in zip(live(new), live(old)):
        assert np.array_equal(a, b)


def test_every_polish_wave_gets_the_budget_in_rows_of_content(monkeypatch):
    """The trap: ``capT // 2`` on the smaller capacity halves the
    candidate set without a word.  Every top-K of a polish wave on a
    merged mesh is at least 1.5x the live tets, as it was at 3x."""
    from parmmg_tpu.ops import edges, swapgen
    from parmmg_tpu.ops.adapt import sliver_polish_impl
    mesh, met = merged_fixture("iso")
    n_t = mesh.np_counts()[1]
    budget = driver.polish_budget(n_t)
    asked = []
    real = edges.wave_budget

    def spy(capT, div=8, rows=None):
        asked.append(real(capT, div, rows))
        return asked[-1]
    monkeypatch.setattr(edges, "wave_budget", spy)
    monkeypatch.setattr(swapgen, "wave_budget", spy)
    wave = jnp.asarray(1000, jnp.int32)
    jax.eval_shape(lambda m, k: sliver_polish_impl(
        m, k, wave, budget=budget), mesh, met)
    assert len(asked) == 4          # collapse, 3-2/2-2, ring, 2-3
    assert min(asked) >= (3 * n_t) // 2 > 2048
    # what the old divisor would ask for on this capacity
    del asked[:]
    jax.eval_shape(lambda m, k: sliver_polish_impl(m, k, wave), mesh, met)
    assert len(asked) == 4 and max(asked) < (3 * n_t) // 2


def test_fem_regrows_at_an_exhausted_capacity():
    """Overflow stays handled: with eight free rows the first fem round
    overflows, the loop regrows (counted) and still converges on the
    mesh the roomy run gives."""
    mesh, met = merged_fixture("iso")
    n_p, n_t = mesh.np_counts()
    info = ParMesh().info
    info.imprim = -1
    info.noswap = info.nomove = True

    def fem_only(m, k):
        stats = AdaptStats()
        otrace.TRACER.reset()
        m, k, stats = driver._finish_run(None, m, k, stats, info,
                                         Timers(), None, None, None)
        rounds = [[r["split"], r["overflow"]] for r in otrace.TRACER.ring
                  if r.get("name") == "fem round"]
        return m, rounds, stats

    tight, tight_met = padded(mesh, met, n_p + 8, n_t + 8)
    roomy, rounds_roomy, stats_roomy = fem_only(mesh, met)
    out, rounds, stats = fem_only(tight, tight_met)
    assert stats_roomy.regrows == 0
    assert stats.regrows >= 1 and any(r[1] for r in rounds)
    assert rounds[-1] == [0, 0]                     # converged
    assert out.capT >= 2 * (n_t + 8)
    assert stats.nsplit == stats_roomy.nsplit > 0
    assert out.np_counts() == roomy.np_counts()
