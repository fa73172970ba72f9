"""A solver's loop through the grouped path (the path the chip runs): a
growth job on a small cube, then two re-adaptations, each staged from
the job before it under the planar shock moved a twentieth of the domain
along x.  ``ParMesh.run`` in three groups, so a middle group frozen on
two seams, on an input whose connectivity the waves made; judged by a
float64 numpy oracle written here.

Nothing else in tier-1 starts from the program's own output: every other
grouped job adapts a generated lattice.
"""
import numpy as np
import pytest

from parmmg_tpu.api.params import IParam
from parmmg_tpu.api.parmesh import ParMesh
from parmmg_tpu.core import constants as C
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.obs.metrics import REGISTRY
from parmmg_tpu.parallel.groups import fresh_cut
from parmmg_tpu.parallel.partition import fix_contiguity, morton_partition
from parmmg_tpu.utils.fixtures import cube_mesh

# 1,296 tets; ceil(ne / 640) is 3 for the lattice (1,296), for the growth
# job's output (1.8k to 1.9k) and for the first re-adaptation's (1.4k)
N, MESH_SIZE = 6, 640
H, DELTA = 0.8, 0.05
OPS = ("adapt.nsplit", "adapt.ncollapse", "adapt.nswap", "tail.polish_ops")


def shock(vert, shift=0.0):
    """benchmarks/metrics/iso_shock.py's size map."""
    return H * (0.2 + 4.0 * np.abs(vert[:, 0] - (0.5 + shift)))


def run_job(vert, tet, met):
    pm = ParMesh()
    pm.set_mesh_size(np_=len(vert), ne=len(tet))
    pm.set_vertices(vert)
    pm.set_tetrahedra(tet + 1)
    pm.set_met_size(1, len(vert))
    pm.set_scalar_mets(met)
    pm.set_iparameter(IParam.meshSize, MESH_SIZE)
    pm.set_iparameter(IParam.niter, 2)
    pm.set_iparameter(IParam.verbose, 0)
    otrace.TRACER.reset()
    before = dict(REGISTRY.snapshot()["counters"])
    assert pm.run() == C.PMMG_SUCCESS
    after = dict(REGISTRY.snapshot()["counters"])
    v, _ = pm.get_vertices()
    t, _ = pm.get_tetrahedra()
    return {"vert": np.asarray(v, np.float64),
            "tet": np.asarray(t, np.int64) - 1, "ntets_in": len(tet),
            "counters": {k: after[k] - before.get(k, 0.0) for k in after},
            "spans": [rec for rec in otrace.TRACER.ring
                      if rec.get("kind") == "span"]}


@pytest.fixture(scope="module")
def loop():
    """[growth job, first re-adaptation, second re-adaptation]."""
    vert, tet = cube_mesh(N)
    jobs = [run_job(vert, tet, shock(vert))]
    for _ in range(2):
        last = jobs[-1]
        jobs.append(run_job(last["vert"], last["tet"],
                            shock(last["vert"], DELTA)))
    return jobs


def spans_named(job, name):
    return [rec for rec in job["spans"]
            if rec["name"].split("/")[-1] == name]


def oracle(vert, tet):
    """What a user can check of a unit cube's mesh, in float64."""
    p = vert[tet]
    vol = np.einsum("ij,ij->i", p[:, 1] - p[:, 0], np.cross(
        p[:, 2] - p[:, 0], p[:, 3] - p[:, 0])) / 6.0
    faces = np.sort(np.concatenate(
        [tet[:, [1, 2, 3]], tet[:, [0, 2, 3]], tet[:, [0, 1, 3]],
         tet[:, [0, 1, 2]]]), axis=1)
    uniq, cnt = np.unique(faces, axis=0, return_counts=True)
    skin = vert[uniq[cnt == 1]]                     # [k, 3, 3]
    # a face with one tet lies in one of the cube's six planes, exactly
    on_cube = np.zeros(len(skin), bool)
    for axis in range(3):
        for side in (0.0, 1.0):
            on_cube |= (skin[:, :, axis] == side).all(axis=1)
    edges = p[:, [0, 0, 0, 1, 1, 2]] - p[:, [1, 2, 3, 2, 3, 3]]
    rms = np.sqrt((edges ** 2).sum(axis=(1, 2)) / 6.0)
    return {"inverted": int((vol <= 0).sum()),
            "overfull": int((cnt > 2).sum()),
            "unmatched_interior": int((~on_cube).sum()),
            "volume": float(vol.sum()),
            # Mmg's Euclidean quality: 1 for the regular tet
            "qmin": float((6.0 * np.sqrt(2.0) * vol / rms ** 3).min())}


@pytest.mark.parametrize("which", [1, 2])
def test_a_job_on_the_programs_own_output_is_conforming(loop, which):
    job = loop[which]
    assert job["ntets_in"] == len(loop[which - 1]["tet"])
    o = oracle(job["vert"], job["tet"])
    assert o["inverted"] == 0 and o["overfull"] == 0, o
    assert abs(o["volume"] - 1.0) <= 1e-4, o
    assert o["qmin"] > 1e-3, o


@pytest.mark.parametrize("which", [1, 2])
def test_the_middle_groups_two_seams_come_back_matched(loop, which):
    """Three groups in both passes, so one of them is frozen on two
    seams and two interfaces are displaced between the passes; every
    face of the output with one tet lies on the cube."""
    job = loop[which]
    splits = spans_named(job, "grp split")
    assert [rec["groups"] for rec in splits] == [3, 3], splits
    assert len(spans_named(job, "grp displace")) == 1
    assert not spans_named(job, "grp regrow")
    o = oracle(job["vert"], job["tet"])
    assert o["unmatched_interior"] == 0, o


@pytest.mark.parametrize("which", [0, 1, 2])
def test_the_count_stands_where_no_ceiling_refuses_the_next_rung(
        loop, which):
    """PR 47 lets the count follow the mesh where a group fills over
    ``-mesh-size`` or a ceiling (``IParam.groupCapacity``) refuses the
    rung a displaced cut would take.  These jobs state no ceiling and
    fill no group: three groups in both passes, the labels the
    displacement handed on, no re-cut, no regrow, and every block one
    dispatch of three live rows, as before."""
    job = loop[which]
    assert not spans_named(job, "grp regrow")
    assert not spans_named(job, "grp recut")
    assert [s["groups"] for s in spans_named(job, "grp split")] == [3, 3]
    for name in ("groups.recuts", "groups.recut_overflow",
                 "groups.regrows", "groups.rows_dead"):
        assert job["counters"].get(name, 0) == 0, name
    blocks = spans_named(job, "grp block")
    assert all((b["tiles"], b["rows"]) == (1, 3) for b in blocks)
    assert job["counters"]["groups.dispatches"] == len(blocks)
    assert job["counters"]["groups.rows"] == 3 * len(blocks)


def test_the_first_readaptation_coarsens_behind_the_front(loop):
    """The front moved: where it was, the input is finer than the new
    map asks for, so collapses lead the splits (a growth job's mix is
    the other way round) and the mesh shrinks."""
    grow, first = loop[0]["counters"], loop[1]["counters"]
    assert grow["adapt.nsplit"] > grow["adapt.ncollapse"]
    assert first["adapt.ncollapse"] > first["adapt.nsplit"]
    assert len(loop[1]["tet"]) < loop[1]["ntets_in"]


def test_the_loop_converges(loop):
    """A second re-adaptation of that output under the SAME metric finds
    less to do than the first, wave by wave and in the merged polish."""
    first, second = loop[1]["counters"], loop[2]["counters"]
    assert sum(second[k] for k in OPS) < sum(first[k] for k in OPS), \
        ([first[k] for k in OPS], [second[k] for k in OPS])


@pytest.mark.parametrize("which", [0, 1, 2])
def test_what_the_quiet_mask_skipped_is_published_every_job(loop, which):
    """``groups.cond_skipped`` is in a job's counters whether or not the
    mask skipped a row, and is the sum of the ``quiet`` field of the
    job's ``grp block`` spans: the rows each dispatch skipped."""
    job = loop[which]
    assert "groups.cond_skipped" in job["counters"]
    blocks = spans_named(job, "grp block")
    assert len(blocks) >= job["counters"]["groups.dispatches"] > 0
    assert all(0 <= rec["quiet"] <= 3 for rec in blocks)
    assert sum(rec["quiet"] for rec in blocks) == \
        job["counters"]["groups.cond_skipped"]
    # a group is marked quiet only by a block in which it did nothing
    assert blocks[0]["quiet"] == 0


@pytest.mark.parametrize("n,groups", [(6, 3), (12, 6), (6, 5), (6, 7)])
def test_a_fresh_cut_stays_even_where_a_relabelled_blob_would_tip_it(
        n, groups):
    """The Morton curve jumps between octants that share no face, so the
    middle part of three (two of six) is two blobs; handing one of them
    to a neighbour leaves a group half as large again, and capacity
    follows the largest group.  The cut keeps the even parts then."""
    vert, tet = cube_mesh(n)
    even = morton_partition(vert[tet].mean(axis=1), groups)
    relabelled = np.bincount(fix_contiguity(tet, even), minlength=groups)
    assert relabelled.max() > 1.2 * len(tet) / groups, relabelled
    part = fresh_cut(vert, tet, groups)
    sizes = np.bincount(part, minlength=groups)
    assert sizes.max() - sizes.min() <= 2, sizes
    assert (part == even).all()


@pytest.mark.parametrize("n,groups", [(4, 2), (6, 2), (6, 4), (16, 2)])
def test_a_fresh_cut_that_is_even_is_the_relabelled_one(n, groups):
    """Two and four parts end on the curve's jumps: every part is one
    blob and the cut is what it always was (the benchmark's 2-group
    cells adapt ``cube_mesh(16)``)."""
    vert, tet = cube_mesh(n)
    want = fix_contiguity(tet, morton_partition(
        vert[tet].mean(axis=1), groups))
    assert (fresh_cut(vert, tet, groups) == want).all()
    assert np.bincount(want).max() <= 1.04 * len(tet) / groups


@pytest.mark.parametrize("n,groups", [(6, 3), (12, 6)])
def test_a_cut_asked_to_be_contiguous_is_the_relabelled_one(n, groups):
    """``contiguous`` (``IParam.contiguousMode``) asks for every group
    in one piece whatever that does to the balance: the relabelled cut
    stands, a group half as large again in it."""
    vert, tet = cube_mesh(n)
    want = fix_contiguity(tet, morton_partition(
        vert[tet].mean(axis=1), groups))
    part = fresh_cut(vert, tet, groups, contiguous=True)
    assert (part == want).all()
    assert np.bincount(part).max() > 1.2 * len(tet) / groups


@pytest.mark.parametrize("value", [0, 1])
def test_contiguous_mode_reaches_the_cut_through_the_api(
        monkeypatch, value):
    """``IParam.contiguousMode`` is what a job's own cut is asked: the
    value set on the ``ParMesh`` arrives at ``fresh_cut`` (which here
    cuts evenly either way, so both values run the fixture's shapes)."""
    from parmmg_tpu.parallel import groups
    asked = []

    def cut(vert_h, tet_h, ngroups, contiguous=False):
        asked.append(contiguous)
        return fresh_cut(vert_h, tet_h, ngroups)
    monkeypatch.setattr(groups, "fresh_cut", cut)
    vert, tet = cube_mesh(N)
    pm = ParMesh()
    pm.set_mesh_size(np_=len(vert), ne=len(tet))
    pm.set_vertices(vert)
    pm.set_tetrahedra(tet + 1)
    pm.set_met_size(1, len(vert))
    pm.set_scalar_mets(shock(vert))
    pm.set_iparameter(IParam.meshSize, MESH_SIZE)
    pm.set_iparameter(IParam.niter, 1)
    pm.set_iparameter(IParam.verbose, 0)
    pm.set_iparameter(IParam.contiguousMode, value)
    assert pm.info.contiguous_mode is bool(value)
    assert pm.run() == C.PMMG_SUCCESS
    assert asked == [bool(value)]


def test_contiguity_is_not_forced_unless_asked():
    assert ParMesh().info.contiguous_mode is False


@pytest.mark.parametrize("target,ok", [
    (C.MG_BDY, True),                                   # surface vertex
    (C.PARBDY_TAGS, False),                             # interior seam
    (C.PARBDY_TAGS | C.MG_PARBDYBDY, True),             # seam on surface
    (0, False)])                                        # interior vertex
def test_a_surface_vertex_collapses_only_onto_a_true_surface_vertex(
        target, ok):
    """A seam vertex carries ``MG_BDY`` with its freeze, interior or
    not; along an edge tagged ``MG_BDY`` (a first pass can leave the bit
    on one slot of an interior edge) a surface vertex may go onto it
    only where it is true boundary (``MG_PARBDYBDY``).  On the chip one
    seed of 23 pulled a vertex of the cube's face into the volume."""
    import jax.numpy as jnp
    from parmmg_tpu.ops.collapse import _removable
    assert C.PARBDY_TAGS & C.MG_BDY
    got = _removable(jnp.uint32(C.MG_BDY), jnp.uint32(target),
                     jnp.uint32(C.MG_BDY))
    assert bool(got) is ok
