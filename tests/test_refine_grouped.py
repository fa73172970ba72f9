"""The group count follows the mesh (the deployment ``cube-shock-iso-refine``
at toy size): two two-pass grouped jobs of ``cube_mesh(6)`` through
``ParMesh.run`` in six groups of 216 tets, one under the size map at
``h = 1.5/n`` (21x the tets at this lattice: ROADMAP B1's reproduction,
8 unmatched interior faces and ``qmin`` 7.1e-4 at 34e868d) and one at
``2.5/n`` (5.5x, the cell's own growth).  Each re-cuts its groups inside
pass 0 (a block filled a group over ``-mesh-size``) and between the
passes (the displaced cut would take a rung over the ceiling the job
states, ``IParam.groupCapacity``, as the deployment does), runs
every block as tiles of the ONE six-row program the first cut compiled,
never regrows, and hands back a mesh ``benchmarks/checker.py`` (numpy,
float64, nothing of the program) finds conforming.  Then, numpy only:
the re-cut itself (``partition.refine_cut``, ``groups._recut_outgrown``
at the accepted cells' own sizes, ``groups.fresh_groups`` under a
ceiling), and the three faults B1 was made of, each at the place it was
made: the repair's stale incidence, the weld's flat tets, a seam edge
frozen in one slot of its shell.
"""
import os
import sys

import numpy as np
import pytest

from parmmg_tpu.api.params import IParam
from parmmg_tpu.api.parmesh import ParMesh
from parmmg_tpu.core import constants as C
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.obs.metrics import REGISTRY
from parmmg_tpu.parallel import distribute, groups, partition
from parmmg_tpu.utils.fixtures import cube_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, ROWS, MESH_SIZE = 6, 6, 216
# the ceiling the jobs state: the rung their first cut takes, three
# times a group of 216 tets (the deployment states 43118 the same way)
CAP_MAX = 746
SCALES = {"h1.5": 1.5, "h2.5": 2.5}
DOMAIN = {"kind": "box", "lo": [0.0] * 3, "hi": [1.0] * 3, "volume": 1.0}


def checker():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        import checker as mod
    finally:
        sys.path.pop(0)
    return mod


def run_job(scale, seed=1):
    """One job as ``benchmarks/job.py`` stages it: the cube's interior
    vertices moved by a twentieth of a cell, the planar-shock size map."""
    vert, tet = cube_mesh(N)
    inner = np.all((vert > 1e-9) & (vert < 1 - 1e-9), axis=1)
    vert = vert.copy()
    vert[inner] += np.random.default_rng(seed).uniform(
        -0.05 / N, 0.05 / N, (int(inner.sum()), 3))
    cuts = []
    real = groups._recut_outgrown

    def watched(vert_h, tet_h, part, *rule):
        out = real(vert_h, tet_h, part, *rule)
        cuts.append((np.array(tet_h), np.array(part), np.array(out)))
        return out
    merged = []
    real_move = partition.move_interfaces

    def watched_move(tet_h, part, nparts, **kw):
        merged.append(np.array(part))
        return real_move(tet_h, part, nparts, **kw)
    pm = ParMesh()
    pm.set_mesh_size(np_=len(vert), ne=len(tet))
    pm.set_vertices(vert)
    pm.set_tetrahedra(tet + 1)
    pm.set_met_size(1, len(vert))
    pm.set_scalar_mets(scale / N * (0.2 + 4.0 * np.abs(vert[:, 0] - 0.5)))
    pm.set_iparameter(IParam.meshSize, MESH_SIZE)
    pm.set_iparameter(IParam.niter, 2)
    pm.set_iparameter(IParam.groupCapacity, CAP_MAX)
    pm.set_iparameter(IParam.verbose, 0)
    otrace.TRACER.reset()
    before = dict(REGISTRY.snapshot()["counters"])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(groups, "_recut_outgrown", watched)
        patch.setattr(partition, "move_interfaces", watched_move)
        assert pm.run() == C.PMMG_SUCCESS
    after = dict(REGISTRY.snapshot()["counters"])
    spans = [r for r in otrace.TRACER.ring if r.get("kind") == "span"]
    out_vert, _ = pm.get_vertices()
    out_tet, _ = pm.get_tetrahedra()
    return {"spans": spans, "ne_in": len(tet),
            "counters": {k: after[k] - before.get(k, 0.0) for k in after},
            "cuts": cuts, "merged": merged,
            "vert": np.asarray(out_vert, np.float64),
            "tet": np.asarray(out_tet, np.int64) - 1,
            "met": np.asarray(pm.get_metric(), np.float64)}


@pytest.fixture(scope="module")
def jobs():
    """Both jobs, the stronger first, in one process that starts with
    nothing compiled; the file lets go of what it compiled."""
    import jax
    jax.clear_caches()
    out = {name: run_job(scale) for name, scale in SCALES.items()}
    yield out
    jax.clear_caches()


def named(job, name):
    return [r for r in job["spans"] if r["name"].split("/")[-1] == name]


@pytest.mark.parametrize("name", list(SCALES))
def test_the_output_is_conforming_and_above_the_cells_floor(jobs, name):
    """What 34e868d failed at ``h = 1.5/n``: no unmatched interior face,
    no face with three tets, no inverted tet, the cube's volume, and
    ``qmin`` over the configuration's 0.001."""
    job = jobs[name]
    nb = checker().measure(job["vert"], job["tet"], job["met"], DOMAIN)
    assert nb["broken"] == 0
    assert (nb["unmatched_interior_faces"], nb["overfull_faces"],
            nb["inverted_tets"]) == (0, 0, 0), nb
    assert nb["volume_rel_err"] < 1e-6
    assert nb["qmin"] > 0.001, nb["qmin"]
    assert nb["ntets"] > 4 * job["ne_in"]
    assert not any(v > 0 for k, v in job["counters"].items()
                   if k.startswith("resilience."))


@pytest.mark.parametrize("name", list(SCALES))
def test_it_recuts_inside_pass_0_and_between_the_passes(jobs, name):
    recuts = named(jobs[name], "grp recut")
    inside = [r for r in recuts if r["why"] == "overflow"]
    between = [r for r in recuts if r["why"] == "pass"]
    assert inside and inside[0]["pass"] == 0 and inside[0]["g0"] == ROWS
    assert len(between) == 1 and between[0]["pass"] == 0
    for r in recuts:
        assert r["g1"] > r["g0"] and r["largest"] <= MESH_SIZE
        assert r["headroom"] >= 0.0 and r["ne"] > 0
    c = jobs[name]["counters"]
    assert c["groups.recuts"] == len(recuts)
    assert c["groups.recut_overflow"] == len(inside)
    # an overflow's span holds its merge and its split, the pass's only
    # the cut
    assert min(r["dur"] for r in inside) > between[0]["dur"]


@pytest.mark.parametrize("name", list(SCALES))
def test_it_never_regrows_and_every_cut_keeps_the_first_cuts_capacity(
        jobs, name):
    job = jobs[name]
    assert not named(job, "grp regrow")
    assert job["counters"].get("groups.regrows", 0) == 0
    splits = named(job, "grp split")
    assert len(splits) == 2 + int(job["counters"]["groups.recut_overflow"])
    assert {(s["capP"], s["capT"]) for s in splits} == \
        {(splits[0]["capP"], splits[0]["capT"])}
    assert splits[0]["groups"] == ROWS
    # no group of a re-cut is over the target, and the count only grows
    assert all(s["largest"] <= MESH_SIZE for s in splits[1:])
    counts = [s["groups"] for s in splits]
    assert counts == sorted(counts) and counts[-1] > 2 * ROWS


def test_one_block_program_a_process_whatever_the_count(jobs):
    """The first job compiles the six-row program; its re-cuts, and the
    whole second job, run it."""
    assert jobs["h1.5"]["counters"]["compile.block_programs"] == 1
    assert jobs["h2.5"]["counters"].get("compile.block_programs", 0) == 0
    progs = {b["prog"] for j in jobs.values() for b in named(j, "grp block")}
    assert len(progs) == 1


@pytest.mark.parametrize("name", list(SCALES))
def test_a_block_is_tiles_of_the_first_cuts_rows(jobs, name):
    job = jobs[name]
    blocks = named(job, "grp block")
    assert all(b["rows"] == ROWS * b["tiles"] for b in blocks)
    assert blocks[0]["tiles"] == 1 and max(b["tiles"] for b in blocks) > 2
    # the rows of a block are the groups of the split before it, padded
    # to whole tiles; the pad rows are the dead ones
    groups_of = []
    for r in job["spans"]:
        leaf = r["name"].split("/")[-1]
        if leaf == "grp split":
            current = r["groups"]
        elif leaf == "grp block":
            groups_of.append(current)
    assert [b["rows"] for b in blocks] == \
        [-(-g // ROWS) * ROWS for g in groups_of]
    c = job["counters"]
    assert c["groups.dispatches"] == sum(b["tiles"] for b in blocks)
    assert c["groups.rows"] == sum(b["rows"] for b in blocks)
    assert c["groups.rows_dead"] == sum(
        b["rows"] - g for b, g in zip(blocks, groups_of)) > 0
    # a dead row is skipped on the device
    assert c["groups.cond_skipped"] >= c["groups.rows_dead"]


@pytest.mark.parametrize("name", list(SCALES))
def test_the_refined_cut_keeps_the_displaced_seams_inside_groups(
        jobs, name):
    """The displacement moved last pass's seams inside groups; the re-cut
    cuts groups inside themselves, so the seams of the displaced cut
    stay seams and most of last pass's stay inside a group."""
    job = jobs[name]
    (tet, displaced, refined), = job["cuts"]
    last, = job["merged"]
    assert np.bincount(refined).max() <= MESH_SIZE < \
        np.bincount(displaced).max()
    i, j = partition.face_pairs(tet)
    was_seam = displaced[i] != displaced[j]
    assert np.all(refined[i][was_seam] != refined[j][was_seam])
    old = last[i] != last[j]
    still = refined[i][old] != refined[j][old]
    assert old.sum() > 0 and still.mean() < 0.5, still.mean()


# ---- the re-cut, numpy only -------------------------------------------------

def uneven_cut(n=6, sizes=(700, 400, 196)):
    vert, tet = cube_mesh(n)
    cent = vert[tet].mean(axis=1)
    order = np.argsort(cent[:, 0], kind="stable")
    part = np.empty(len(tet), np.int32)
    part[order] = np.repeat(np.arange(len(sizes)), sizes)
    return vert, tet, cent, part


@pytest.mark.parametrize("target", [100, 216, 250, 500])
def test_refine_cut_leaves_no_group_over_the_target(target):
    vert, tet, cent, part = uneven_cut()
    out = partition.refine_cut(vert, tet, part, target)
    sizes = np.bincount(part)
    assert np.bincount(out).max() <= target
    assert len(np.bincount(out)) == sum(-(-s // target) for s in sizes)
    assert np.bincount(out).min() > 0
    # cut inside itself: a group of the new cut lies in one of the old
    for g in range(out.max() + 1):
        assert len(set(part[out == g].tolist())) == 1
    # and every old seam is a seam still
    i, j = partition.face_pairs(tet)
    seam = part[i] != part[j]
    assert np.all(out[i][seam] != out[j][seam])


def test_refine_cut_leaves_a_cut_that_fits_as_it_is():
    vert, tet, cent, part = uneven_cut()
    assert np.array_equal(partition.refine_cut(vert, tet, part, 700), part)
    out = partition.refine_cut(vert, tet, part, 400)
    assert np.array_equal(out[part != 0], part[part != 0])
    assert set(out[part == 0].tolist()) == {0, 3}


def test_cut_sizes_counts_the_fullest_groups_vertices_and_tets():
    vert, tet, cent, part = uneven_cut()
    by_hand = max(len(np.unique(tet[part == g])) for g in range(3))
    assert partition.cut_sizes(tet, part) == (by_hand, 700)


# the accepted cells' second cuts as their configurations' ``assumed``
# state them: groups, the fullest group's tets, the capacity's tets.
# Each fits its kept capacity with REUSE_SLACK (30,793-31,475 of 34,494
# in iso-scale6), so the displaced labels stand untouched, though every
# one of these groups is over -mesh-size 16384
ACCEPTED = {"iso-growth": (2, 21000, 43118),
            "iso-readapt": (3, 20000, 43118),
            "iso-scale6": (6, 31475, 43118)}


@pytest.mark.parametrize("cell", list(ACCEPTED))
def test_a_displaced_cut_that_fits_its_capacity_is_not_recut(cell):
    from parmmg_tpu.utils.compilecache import bucket
    ngroups, fullest, capT = ACCEPTED[cell]
    assert distribute.shard_capacity(1, fullest, keep=(64, capT))[1] == capT
    # the same cut at a hundredth of the size, on the rungs it then takes
    vert, tet = cube_mesh(6)
    cent = vert[tet].mean(axis=1)
    big = fullest // 100
    rest = (len(tet) - big) // (ngroups - 1)
    sizes = [big] + [rest] * (ngroups - 2)
    sizes.append(len(tet) - sum(sizes))
    order = np.argsort(cent[:, 0], kind="stable")
    part = np.empty(len(tet), np.int32)
    part[order] = np.repeat(np.arange(ngroups), sizes)
    most_verts, largest = partition.cut_sizes(tet, part)
    caps = tuple(bucket(int(np.ceil(distribute.REUSE_SLACK * n)), floor=64,
                        scheme="geo") for n in (most_verts, largest))
    target = 16384 // 100
    assert largest > target             # over -mesh-size, as the cells' are
    otrace.TRACER.reset()
    assert groups._recut_outgrown(vert, tet, part, target, caps,
                                  caps[1]) is part
    # past the capacity's edge the same cut takes the next rung where no
    # ceiling refuses it (every job until PR 47, and every accepted one)
    tight = (caps[0], largest)
    assert distribute.shard_capacity(most_verts, largest,
                                     keep=tight)[1] > largest
    assert groups._recut_outgrown(vert, tet, part, target, tight) is part
    assert groups._recut_outgrown(vert, tet, part, target, tight,
                                  10 * largest) is part
    assert not [r for r in otrace.TRACER.ring if r.get("name") == "grp recut"]
    # and is re-cut where one does
    out = groups._recut_outgrown(vert, tet, part, target, tight, largest)
    assert out.max() + 1 > ngroups and np.bincount(out).max() <= target
    span, = [r for r in otrace.TRACER.ring if r.get("name") == "grp recut"]
    assert (span["why"], span["g0"], span["g1"]) == \
        ("pass", ngroups, out.max() + 1)
    # with no capacity kept (a resumed run) the labels stand
    assert groups._recut_outgrown(vert, tet, part, target, None,
                                  largest) is part


@pytest.mark.parametrize("ne,target,cap_max,expect", [
    (82944, 16384, 0, 6),           # the cells' first cut, no ceiling
    (82944, 16384, 43118, 6),       # 3 x 13,824 is on rung 43118
    (98304, 16384, 43118, 7),       # 6 x 16,384 would take rung 64678
    (24576, 16384, 43118, 2),
    (1296, 216, 746, 6),
    (1296, 216, 500, 8),
])
def test_a_first_cut_takes_more_groups_under_a_ceiling(ne, target, cap_max,
                                                       expect):
    got = groups.fresh_groups(ne, target, cap_max)
    assert got == expect
    if cap_max:
        assert distribute.shard_capacity(1, -(-ne // got))[1] <= cap_max


def test_group_capacity_is_a_parameter_of_the_api():
    pm = ParMesh()
    assert pm.info.group_capacity == 0
    pm.set_iparameter(IParam.groupCapacity, 43118)
    assert pm.info.group_capacity == 43118


def test_a_regrow_stops_at_the_ceiling():
    """Groups under the target in a capacity set by hand overflow into
    the next rung (the arm that stays); under a ceiling at the capacity
    they have, that is the driver's LOWFAILURE and not a compile."""
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    import jax.numpy as jnp
    vert, tet = cube_mesh(3)
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    met = jnp.full(mesh.capP, 0.08, mesh.vert.dtype)
    with pytest.raises(MemoryError, match="ceiling"):
        groups.grouped_adapt_pass(mesh, met, 2, cycles=6, cap_mult=1.2,
                                  target=10 ** 6, cap_max=100)


# ---- B1's three faults, each where it was made ------------------------------

def test_the_repair_keeps_its_incidence_true_after_a_swap():
    """``sequential_repair`` kept a vertex -> tets map and never took a
    rewritten tet out of the sets of the vertices it lost, so a later
    collapse counted a tet that no longer held the removed vertex among
    the dying ones and opened a hole: the 8 unmatched faces of ROADMAP
    B1.  The case is that job's mesh at the repair's entry, cut down to
    the tets within two vertices of a bad one (6,498 tets, 94 of them
    under 1e-3; the cut's own boundary vertices required, so that it
    stays): 34e868d's repair leaves 8 faces with one tet that had two
    and loses 1e-6 of the volume."""
    from parmmg_tpu.ops import repair
    with np.load(os.path.join(ROOT, "tests", "data",
                              "b1_repair_case.npz")) as f:
        case = dict(f)
    chk = checker()

    def lone_faces(tet):
        uniq, cnt = chk.face_counts(tet)
        assert not (cnt > 2).any()
        return {tuple(f) for f in uniq[cnt == 1].tolist()}
    before = case["tet"][case["tmask"]]
    out = repair.sequential_repair(**case)
    vert, tet, tmask = out[0], out[1], out[2]
    assert out[-1] >= 90                    # it repaired them
    assert lone_faces(tet[tmask]) == lone_faces(before)
    vol = chk.volumes(vert[tet[tmask]].astype(np.float64))
    vol0 = chk.volumes(case["vert"][before].astype(np.float64))
    assert (vol > 0).all() and abs(vol.sum() - vol0.sum()) < 1e-12


def test_a_weld_may_not_flatten_a_tet():
    """``merge_shards`` welds near-coincident vertex pairs, and took any
    weld that left positive volumes: one that moves a vertex into the
    plane of a tet's other three leaves a tet of 1e-7.  Held to the
    collapse's gate (0.3 of the worst it rewrote) it is refused, and the
    pair is welded the other way or left."""
    from parmmg_tpu.ops.repair import _qual as repair_quality
    vert = np.array([[0, 0, 0], [1, 0, 0], [0, 1, 0], [0.3, 0.3, 1.0],
                     [0.3, 0.3, 1e-7 + 0.02], [0.3, 0.3, 1e-7]], float)
    # 5 sits a hair over the base; 4 close above it: welding 4 onto 5
    # flattens the tet (0, 1, 2, 4)
    tet = np.array([[0, 1, 2, 4], [0, 1, 4, 3], [1, 2, 4, 3], [2, 0, 4, 3]])
    vtag = np.zeros(6, np.uint32)
    met = np.full(6, 1.0)
    zeros = lambda k: np.zeros((len(tet), k), np.uint32)  # noqa: E731
    q = repair_quality(vert[tet])
    assert q.min() > 0.0
    moved = np.where(tet == 4, 5, tet)[[0]]
    assert 0 < repair_quality(vert[moved]).min() < 1e-5
    new_tet, vkeep, tkeep = distribute._weld_close_pairs(
        vert, tet, vtag, met, np.ones(len(tet), np.int32), zeros(4),
        zeros(6))
    kept = new_tet[tkeep]
    assert repair_quality(vert[kept]).min() >= 0.3 * q.min()


def test_a_seam_edge_is_frozen_in_every_slot_of_its_shell():
    """``split_to_shards`` tagged a seam edge in the slots of the tets
    that own one of its seam faces; a swap routes a new tet's edge tag
    from ONE old slot, so a tet that came to own the seam face could
    carry the edge unfrozen and the split took it.  Every slot of every
    seam edge carries the freeze."""
    from parmmg_tpu.core.mesh import make_mesh, mesh_to_host
    from parmmg_tpu.ops.analysis import analyze_mesh
    import jax.numpy as jnp
    vert, tet = cube_mesh(4)
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    met = jnp.full(mesh.capP, 0.3, mesh.vert.dtype)
    _, tet_h, _, _, _ = mesh_to_host(mesh)
    part = groups.fresh_cut(vert, tet_h, 3)
    stacked, _, l2g = distribute.split_to_shards(mesh, met, part, 3,
                                                 return_l2g=True)
    i, j = partition.face_pairs(tet_h)
    cross = part[i] != part[j]
    faces = []
    for t, u in zip(i[cross], j[cross]):
        faces.append(sorted(set(tet_h[t]) & set(tet_h[u])))
    seam = {tuple(sorted((f[a], f[b]))) for f in faces
            for a, b in ((0, 1), (0, 2), (1, 2))}
    iare = np.asarray(C.IARE)
    frozen = C.MG_PARBDY | C.MG_REQ
    seen = 0
    for g in range(3):
        tm = np.asarray(stacked.tmask[g])
        glob = np.asarray(l2g[g])[np.asarray(stacked.tet[g])[tm]]
        etag = np.asarray(stacked.etag[g])[tm]
        for e in range(6):
            a, b = glob[:, iare[e, 0]], glob[:, iare[e, 1]]
            on = np.array([(min(x, y), max(x, y)) in seam
                           for x, y in zip(a.tolist(), b.tolist())])
            assert np.all((etag[on, e] & frozen) == frozen)
            assert not np.any(etag[~on, e] & C.MG_PARBDY)
            seen += int(on.sum())
    # more slots than the three a seam face gives its owner
    assert seen > 3 * 2 * len(faces)
