"""One block program a job shape (PR 31).

jax keys a lowering on its arguments' shardings, and an uncommitted
argument (a fresh ``jnp.zeros``) lowers with an unspecified one.  The
grouped pass made its incremental-topology state that way beside a
committed stacked state, and the block program hands the state back
committed: dispatch 0 of every pass ran one executable and every later
dispatch another, same jaxpr, each minutes of compile and 115 MB of
cache at the benchmark's capacity (PERF.md, PR 31).  The state left the
blocks with PR 46; what a pass hands its first block is still committed,
and still ONE tree.  Here on the CPU, on
``cube_mesh(3)`` in two groups: the executables the compile ledger
counts for ``groups.adapt_block``, the job's output against the
parent's, and the detector that names such a variant wherever a
governed program meets one (``compile.placement_variants``).

A block is one cycle (PR 32): a pass of ``cycles`` cycles dispatches
``cycles`` blocks through that one executable whichever of them swap or
bypass the split prescreen, and under ``-noswap`` none swaps.

The five grouped runs compile once for the module (about two minutes),
each with an empty block cache, so no other module's programs are met
or left behind.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.obs.metrics import REGISTRY
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.parallel import groups
from parmmg_tpu.utils import compilecache, placement
from parmmg_tpu.utils.fixtures import cube_mesh

BLOCK = "groups.adapt_block"
# the two-pass job below at the parent commit f23d160 (CPU, my run, PR 31)
PARENT_JOB = {
    "ntets": 198, "nverts": 73,
    "sha256": ("dfe58302751f0f928899030cf56a721e"
               "8388f318721e680a415116a47e8602fc"),
}


def toy(h_near):
    """``cube_mesh(3)`` (162 tets) under a size of ``h_near`` where
    x < 0.2 and 0.45, which asks for nothing, elsewhere."""
    vert, tet = cube_mesh(3)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.where(m.vert[:, 0] < 0.2, h_near, 0.45).astype(m.vert.dtype)
    return m, met, len(tet)


def digest(mesh, met):
    tm, vm = np.asarray(mesh.tmask), np.asarray(mesh.vmask)
    h = hashlib.sha256()
    for a in (np.asarray(mesh.tet)[tm], np.asarray(mesh.vert)[vm],
              np.asarray(met)[vm]):
        h.update(np.ascontiguousarray(a).tobytes())
    return {"ntets": int(tm.sum()), "nverts": int(vm.sum()),
            "sha256": h.hexdigest()}


def counters():
    snap = REGISTRY.snapshot()["counters"]
    return {k: snap.get(k, 0.0) for k in ("compile.block_programs",
                                          "compile.placement_variants",
                                          "groups.dispatches")}


@pytest.fixture(scope="module")
def runs():
    mp = pytest.MonkeyPatch()
    out = {}
    committed = []              # bytes of each tree a run commits
    to_device = placement.to_device

    def spy(tree):
        committed.append(sum(a.nbytes for a in jax.tree.leaves(tree)))
        return to_device(tree)
    mp.setattr(placement, "to_device", spy)

    def measured(name, fn):
        # an empty block cache: a jit of its own, compiled afresh
        mp.setattr(groups, "_GROUP_BLOCK_CACHE", {})
        committed.clear()
        compilecache.reset_ledger()
        otrace.TRACER.configure(path=None)
        otrace.TRACER.reset()
        before = counters()
        res = fn()
        recs = list(otrace.TRACER.ring)
        out[name] = {
            "result": res,
            "ledger": compilecache.ledger_snapshot()[BLOCK],
            "counters": {k: v - before[k] for k, v in counters().items()},
            "blocks": [r for r in recs if r.get("name") == "grp block"],
            "regrows": [r for r in recs if r.get("name") == "grp regrow"],
            "uploads": [r for r in recs if r.get("name") == "grp upload"],
            "splits": [r for r in recs if r.get("name") == "grp split"],
            "committed": list(committed),
            "variants": [r for r in recs if r.get("name") == "compile"
                         and "variant" in r],
        }

    def one_pass(noswap=False):
        m, met, _ = toy(0.3)
        mesh, met, _ = groups.grouped_adapt_pass(m, met, 2, cycles=3,
                                                 noswap=noswap)
        return digest(mesh, met)

    def job(noswap=False):
        m, met, ne = toy(0.3)
        mesh, met = groups.grouped_adapt(m, met, target_size=ne // 2,
                                         niter=2, cycles=3, noswap=noswap)
        return digest(mesh, met)

    def regrown_pass():
        # groups of 81 tets in a capacity of 97 under a size that asks
        # for three times as many: the blocks overflow and the pass
        # regrows
        vert, tet = cube_mesh(3)
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.full(m.capP, 0.3, m.vert.dtype)
        mesh, met, _ = groups.grouped_adapt_pass(m, met, 2, cycles=3,
                                                 cap_mult=1.05)
        return digest(mesh, met)

    try:
        measured("pass", one_pass)
        measured("job", job)
        measured("pass-noswap", lambda: one_pass(noswap=True))
        measured("job-noswap", lambda: job(noswap=True))
        measured("regrow", regrown_pass)
    finally:
        mp.undo()
        otrace.TRACER.reset()
    return out


@pytest.mark.parametrize("noswap", [False, True])
@pytest.mark.parametrize("which,dispatches", [("pass", 3), ("job", 6)])
def test_one_executable_a_job_shape(runs, which, dispatches, noswap):
    """A block is one cycle: the three cycles of a pass, and the six of
    a two-pass job whose second pass keeps the first one's capacity,
    are as many dispatches of ONE executable (PR 31's parent built two:
    dispatch 0 of a pass met an uncommitted state), whether a cycle
    swaps (the last two of a pass) or not, bypasses the split prescreen
    (the same two) or not, and under ``-noswap``, where none swaps."""
    run = runs[which + ("-noswap" if noswap else "")]
    assert len(run["blocks"]) == run["ledger"]["calls"] == dispatches
    assert run["counters"]["groups.dispatches"] == dispatches
    assert [r["block"] for r in run["blocks"]] == \
        list(range(3)) * (dispatches // 3)
    assert all("nblk" not in r for r in run["blocks"])
    assert run["ledger"]["shapes_seen"] == 1
    assert run["ledger"]["compiles"] == 1
    assert run["counters"]["compile.block_programs"] == 1
    assert run["counters"]["compile.placement_variants"] == 0
    if noswap:
        assert sum(r["swap"] for r in run["blocks"]) == 0


def test_a_regrown_pass_builds_one_executable_a_capacity(runs):
    run = runs["regrow"]
    assert run["regrows"], "the pass was meant to overflow and regrow"
    caps = {r["capT0"] for r in run["regrows"]} \
        | {r["capT1"] for r in run["regrows"]}
    assert len(caps) == len(run["regrows"]) + 1
    assert run["ledger"]["shapes_seen"] == len(caps)
    assert run["ledger"]["compiles"] == len(caps)
    assert run["counters"]["compile.block_programs"] == len(caps)
    assert run["result"]["ntets"] > 162


def test_the_job_equals_the_parents(runs):
    """Zeros are zeros wherever they live: the two-pass job hands back
    the mesh it handed back at the parent commit."""
    assert runs["job"]["result"] == PARENT_JOB


def test_the_upload_span_counts_the_one_tree_a_pass_commits(runs):
    """A pass commits ONE tree, the stacked mesh with its metric (no
    table state beside it since PR 46), and ``grp upload``'s ``bytes``
    hold it."""
    run = runs["pass"]
    (up,) = run["uploads"]
    (state,) = run["committed"]
    assert up["bytes"] == state > 0


def test_no_placement_variant_in_a_grouped_job(runs):
    for which in ("pass", "job", "regrow"):
        assert runs[which]["counters"]["compile.placement_variants"] == 0
        assert runs[which]["variants"] == []
        assert runs[which]["ledger"]["placement_variants"] == 0


def test_a_placement_variant_is_counted_and_named():
    """A governed toy program called with one argument uncommitted and
    then committed: jax lowers it twice, the ledger counts ONE placement
    variant and the second executable's ``compile`` event names the leaf
    and the open span; the same call again, and other shapes, count
    nothing."""
    compilecache.reset_ledger()
    otrace.TRACER.configure(path=None)
    otrace.TRACER.reset()

    @compilecache.governed("test.placement_toy")
    @jax.jit
    def toy_program(state, x):
        return {"acc": state["acc"] + x.sum()}, x * 2

    dev = jax.devices()[0]
    x = jax.device_put(jnp.arange(8.0), dev)            # committed
    before = counters()
    with otrace.span("toy dispatch") as sp:
        state = {"acc": jnp.zeros(())}                  # uncommitted
        for _ in range(3):      # the program hands the state back committed
            state, _ = toy_program(state, x)
    assert float(state["acc"]) == 3 * 28.0
    row = compilecache.ledger_snapshot()["test.placement_toy"]
    assert row["calls"] == 3 and row["variants"] == 1
    assert row["compiles"] == 2 and row["placement_variants"] == 1
    now = counters()
    assert now["compile.placement_variants"] \
        - before["compile.placement_variants"] == 1
    events = [r for r in otrace.TRACER.ring if r.get("name") == "compile"
              and r["fun"] == "jit(toy_program)"]
    assert [("variant" in r) for r in events] == [False, True]
    ev = events[1]
    assert ev["variant"] == "placement" and ev["parent"] == sp.id
    assert len(ev["leaves"]) == 1
    assert ev["leaves"][0].startswith("[0][0]['acc']: uncommitted -> ")
    # another shape is a real variant, not a placement one; a fresh
    # uncommitted state at the first shape meets the first executable
    toy_program({"acc": jnp.zeros(())}, jax.device_put(jnp.ones(16), dev))
    toy_program({"acc": jnp.zeros(())}, x)
    row = compilecache.ledger_snapshot()["test.placement_toy"]
    assert row["variants"] == 2 and row["compiles"] == 3
    assert row["placement_variants"] == 1
    otrace.TRACER.reset()
