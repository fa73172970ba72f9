"""One whole job through the SPMD path as a caller reaches it: ranks x
groups on four (virtual) devices, staged through the API with
``IParam.nDevices`` and a ``meshSize`` that gives two groups a rank,
``ParMesh.run()`` -> ``driver.parmmg_run`` -> ``distributed_adapt_multi``
-> the SPMD block, refresh, displacement, band migration, ONE merge, the
merged tail.  Judged by the benchmark's plain reference
(``benchmarks/checker.py``: numpy, float64, imports nothing of the
program) on every exact guarantee of ``cube-shock-iso``.

Everything else that runs this path end to end is marked slow; this file
is not.  The job is shared by a module fixture (compiles are its cost).
"""
import json
import os
import sys

import numpy as np
import pytest

from parmmg_tpu.api.params import (IParam, Info, InputError, check_devices,
                                   groups_per_rank)
from parmmg_tpu.api.parmesh import ParMesh
from parmmg_tpu.core import constants as C
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.obs.metrics import REGISTRY
from parmmg_tpu.utils.fixtures import cube_mesh

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
# 3,072 tets: a rank's share is 768, two groups of MESH_SIZE (at 1,296
# tets the seams of eight parts are most of the mesh, and the two paths
# end 18 % apart)
N, MESH_SIZE, NDEV = 8, 400, 4
H = 3.2 / N
BANDS = ("ntets", "len_ok_share")


def shock(vert):
    """benchmarks/metrics/iso_shock.py's size map."""
    return H * (0.2 + 4.0 * np.abs(vert[:, 0] - 0.5))


@pytest.fixture(scope="module")
def reference():
    """(checker, cube-shock-iso's domain and exact guarantees)."""
    sys.path.insert(0, BENCH)
    try:
        import checker
        with open(os.path.join(BENCH, "configs", "cube-shock-iso.json")) as f:
            cfg = json.load(f)
        yield checker, cfg["domain"], {
            k: v for k, v in cfg["guarantees"].items() if k not in BANDS}
    finally:
        sys.path.remove(BENCH)


def jittered_cube():
    """The lattice with its interior vertices moved by up to a twentieth
    of a cell (benchmarks/inputs.build_input's rule): a dyadic lattice
    coordinate has few significand bits, which the checker would read
    as a result stored in a short type."""
    vert, tet = cube_mesh(N)
    inner = ((vert > 0.0) & (vert < 1.0)).all(axis=1)
    vert = vert.copy()
    vert[inner] += np.random.default_rng(1).uniform(
        -0.05 / N, 0.05 / N, (int(inner.sum()), 3))
    return vert, tet


def run_job(ndev=None, mesh_size=MESH_SIZE):
    vert, tet = jittered_cube()
    pm = ParMesh()
    pm.set_mesh_size(np_=len(vert), ne=len(tet))
    pm.set_vertices(vert)
    pm.set_tetrahedra(tet + 1)
    pm.set_met_size(1, len(vert))
    pm.set_scalar_mets(shock(vert))
    pm.set_iparameter(IParam.meshSize, mesh_size)
    pm.set_iparameter(IParam.niter, 2)
    pm.set_iparameter(IParam.verbose, 0)
    if ndev is not None:
        pm.set_iparameter(IParam.nDevices, ndev)
    otrace.TRACER.reset()
    before = dict(REGISTRY.snapshot()["counters"])
    rc = pm.run()
    after = dict(REGISTRY.snapshot()["counters"])
    v, _ = pm.get_vertices()
    t, _ = pm.get_tetrahedra()
    return {"rc": rc, "vert": np.asarray(v), "tet": np.asarray(t) - 1,
            "met": np.asarray(pm.get_metric()),
            "counters": {k: after[k] - before.get(k, 0.0) for k in after},
            "spans": [r for r in otrace.TRACER.ring
                      if r.get("kind") == "span"]}


@pytest.fixture(scope="module")
def jobs():
    """The SPMD job (4 ranks x 2 groups) and the same input through the
    grouped path with the parameter never set and set to 1."""
    import jax
    from parmmg_tpu.parallel import dist
    assert len(jax.devices()) >= NDEV       # tests/conftest.py: 8
    # the SPMD job as a process that holds chips runs it: the block lists
    # its surface scatters, and what runs between two iterations is
    # staged on the host (``host_between``).  The slow files run the
    # other placement, everything on the (virtual) devices
    placed, dist.placed_on_tpu = dist.placed_on_tpu, lambda: True
    try:
        spmd = run_job(NDEV)
    finally:
        dist.placed_on_tpu = placed
    return {"spmd": spmd, "unset": run_job(), "one": run_job(1)}


def spans_named(job, name):
    return [r for r in job["spans"] if r["name"].split("/")[-1] == name]


def test_the_job_meets_every_exact_guarantee_of_cube_shock_iso(
        jobs, reference):
    checker, domain, guarantees = reference
    job = jobs["spmd"]
    numbers = checker.measure(job["vert"], job["tet"], job["met"], domain)
    numbers["degraded"] = int(job["rc"] != C.PMMG_SUCCESS) + sum(
        int(v > 0) for k, v in job["counters"].items()
        if k.startswith("resilience."))
    rows = checker.judge(numbers, guarantees)
    assert {r["name"] for r in rows} == set(guarantees)
    assert [r for r in rows if not r["ok"]] == [], numbers


def test_every_vertex_handed_back_is_in_a_tet_and_has_a_size(jobs):
    job = jobs["spmd"]
    used = np.zeros(len(job["vert"]), bool)
    used[job["tet"].ravel()] = True
    assert used.all(), f"{int((~used).sum())} vertices belong to no tet"
    assert len(job["met"]) == len(job["vert"])
    assert (job["met"] > 0).all()


def test_the_job_ends_where_the_grouped_paths_does(jobs):
    n_spmd, n_grp = len(jobs["spmd"]["tet"]), len(jobs["unset"]["tet"])
    assert n_grp > 6 * N ** 3                   # the job adapted
    assert abs(n_spmd - n_grp) <= 0.05 * n_grp, (n_spmd, n_grp)


def test_the_driver_cut_ranks_x_groups(jobs):
    """``meshSize`` means on four devices what it means on one: two
    groups a rank, eight shards, G rows of the block a device."""
    job = jobs["spmd"]
    (split,) = spans_named(job, "dist split")
    assert (split["shards"], split["G"]) == (2 * NDEV, 2)
    assert 384 <= split["largest"] <= MESH_SIZE
    assert split["capT"] >= 3 * split["largest"]
    assert job["counters"]["dist.devices"] == NDEV
    assert not spans_named(job, "grp split")
    # and with one device the grouped path, as ever
    assert spans_named(jobs["unset"], "grp split")
    assert not spans_named(jobs["unset"], "dist split")


def test_the_loop_is_spans_under_adaptation(jobs):
    job = jobs["spmd"]
    (adaptation,) = spans_named(job, "adaptation")
    for name, count in (("dist split", 1), ("dist refresh", 2),
                        ("dist displace", 1), ("dist migrate", 1),
                        ("dist merge", 1)):
        recs = spans_named(job, name)
        assert len(recs) == count, name
        assert all(r["parent"] == adaptation["id"] for r in recs), name
    (mig,) = spans_named(job, "dist migrate")
    assert mig["moved_tets"] > 0 and mig["band_rows"] > 0
    assert mig["bytes"] >= 172 * mig["moved_tets"]
    # staged on the host: every shard pulled after an iteration's blocks
    # and pushed back once, before the next
    pulls = [r["pull_bytes"] for r in spans_named(job, "dist refresh")]
    assert len(pulls) == 2 and min(pulls) > 0
    assert mig["push_bytes"] == pulls[1]
    (merge,) = spans_named(job, "dist merge")
    assert merge["ne"] > 0
    blocks = spans_named(job, "dist block")
    assert [b["it"] for b in blocks] == sorted(b["it"] for b in blocks)
    assert {b["it"] for b in blocks} == {0, 1}
    assert all(b["parent"] == adaptation["id"] for b in blocks)
    (split,) = spans_named(job, "dist split")
    assert blocks[0]["live"] == 6 * N ** 3
    assert blocks[0]["largest"] == split["largest"]


def test_the_counters_of_a_job_zeros_too(jobs):
    c = jobs["spmd"]["counters"]
    blocks = spans_named(jobs["spmd"], "dist block")
    assert c["dist.dispatches"] == len(blocks)
    assert c["dist.pipeline.compute_s"] == pytest.approx(
        sum(b["dur"] for b in blocks))
    (mig,) = spans_named(jobs["spmd"], "dist migrate")
    assert c["dist.migrated_tets"] == mig["moved_tets"]
    assert c["dist.exchange_bytes"] > mig["bytes"]      # and the halos
    # the last iteration's entry: its first block's reading
    first = next(b for b in blocks if b["it"] == 1)
    assert c["dist.live_tets"] == first["live"]
    assert c["dist.largest_shard"] == first["largest"]
    assert c["dist.largest_shard"] * 8 >= c["dist.live_tets"]
    # ONE SPMD block program a capacity: at this size the displacement
    # sweeps half the mesh into one shard, whose arrivals outgrow the
    # first capacity (the cell's size keeps its rung: PERF.md section 4)
    assert 1 <= c["compile.block_programs"] <= 2
    # the grouped job published none of them
    assert "dist.dispatches" not in jobs["unset"]["counters"] or \
        jobs["unset"]["counters"]["dist.dispatches"] == 0


def test_the_surface_columns_reach_the_counters_from_the_spmd_block(jobs):
    """Columns 7-10 of the cycle's counts row, which the SPMD block used
    to drop: the cube's faces are a surface, a fifth of the splits are
    boundary edges and the smoother slides vertices along them."""
    job = jobs["spmd"]
    blocks = spans_named(job, "dist block")
    for key in ("bsplit", "hveto", "bmoved", "listed"):
        assert all(key in b for b in blocks), key
    assert sum(b["bsplit"] for b in blocks) > 0
    assert sum(b["bmoved"] for b in blocks) > 0
    c = job["counters"]
    # the job's counters hold the blocks' sums and the tail's besides
    assert c["surf.bsplit"] >= sum(b["bsplit"] for b in blocks) > 0
    assert c["surf.bmoved"] >= sum(b["bmoved"] for b in blocks) > 0
    assert c["surf.hveto"] >= sum(b["hveto"] for b in blocks)
    assert c["adapt.nsplit"] >= sum(b["split"] for b in blocks) > 0
    # the lists engaged in every row that ran, and held a few percent of
    # what the scatters are at full width
    assert c["surf.listed"] == sum(b["listed"] for b in blocks) > 0
    assert 0 < c["surf.listed"] < 0.1 * c["surf.list_full"]


def test_one_device_set_is_the_job_with_the_parameter_never_set(jobs):
    a, b = jobs["unset"], jobs["one"]
    assert a["rc"] == b["rc"] == C.PMMG_SUCCESS
    for key in ("vert", "tet", "met"):
        assert a[key].dtype == b[key].dtype
        assert np.array_equal(a[key], b[key]), key


@pytest.mark.parametrize("ne,ndev,mesh_size,groups", [
    (93750, 4, 16384, 2),       # the cell: a rank's 23.4k in two groups
    (93750, 1, 16384, 6),       # one rank: the grouped path's own count
    (48000, 4, 16384, 1),       # a rank's share under the target
    (3072, 4, 400, 2),          # this file's job
    (1296, 4, 162, 2), (1296, 4, 161, 3),
    (93750, 4, C.TARGET_MESH_SIZE_SENTINEL, 1),     # the default: no cut
    (93750, 4, -1, 1), (10, 4, -1, 1),
    (93750, 4, 0, 100),         # clamped to REDISTR_NELEM_MIN, then 100
    (93750, 4, 1, 100), (24, 4, 1, 1), (28, 4, 1, 2),
    (5, 8, 16384, 1),           # fewer tets than ranks
])
def test_the_shard_count_rule(ne, ndev, mesh_size, groups):
    assert groups_per_rank(ne, ndev, mesh_size) == groups


def test_more_devices_than_jax_has_is_refused_at_run():
    import jax
    have = len(jax.devices())
    with pytest.raises(InputError):
        check_devices(Info(n_devices=have + 1), have)
    with pytest.raises(InputError):
        check_devices(Info(n_devices=0), have)
    check_devices(Info(n_devices=have), have)
    vert, tet = cube_mesh(2)
    pm = ParMesh()
    pm.set_mesh_size(np_=len(vert), ne=len(tet))
    pm.set_vertices(vert)
    pm.set_tetrahedra(tet + 1)
    pm.set_met_size(1, len(vert))
    pm.set_scalar_mets(np.full(len(vert), 0.3))
    pm.set_iparameter(IParam.verbose, -1)
    pm.set_iparameter(IParam.nDevices, have + 1)
    assert pm.info.n_devices == have + 1
    otrace.TRACER.reset()
    assert pm.run() == C.PMMG_STRONGFAILURE
    # refused before anything ran: no run span, no result to pull
    assert not [r for r in otrace.TRACER.ring if r.get("name") == "run"]
    with pytest.raises(RuntimeError):
        pm.get_vertices()


def test_the_cli_sets_the_count_through_the_parameter(monkeypatch, tmp_path):
    """``-ndev`` goes through ``set_iparameter(IParam.nDevices, ...)``."""
    from parmmg_tpu import cli
    seen = []
    orig = ParMesh.set_iparameter

    def spy(self, key, val):
        seen.append((key, val))
        return orig(self, key, val)
    monkeypatch.setattr(ParMesh, "set_iparameter", spy)
    monkeypatch.setattr(ParMesh, "run", lambda self: C.PMMG_STRONGFAILURE)
    from parmmg_tpu.io.medit import MeditMesh, write_mesh
    vert, tet = cube_mesh(1)
    m = MeditMesh()
    m.vert = vert.astype(np.float64)
    m.vref = np.zeros(len(vert), np.int32)
    m.tetra = tet.astype(np.int32)
    m.tref = np.ones(len(tet), np.int32)
    write_mesh(str(tmp_path / "c.mesh"), m)
    cli.main(["-in", str(tmp_path / "c.mesh"), "-out",
              str(tmp_path / "o.mesh"), "-hsiz", "0.5", "-ndev", "3",
              "-v", "-1"])
    assert (IParam.nDevices, 3) in seen
