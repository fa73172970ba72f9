"""The cell ``iso-scale6``'s job on the CPU at a small size (the cube at
``n`` 6 in six groups of ``meshSize`` 216, as tests/test_scale6_grouped.py
has it): the configuration is ``cube-shock-iso``'s but for what issue 43
lists, a job staged as ``run.py`` stages it meets every exact guarantee,
runs six groups in both passes without a regrow, and leaves in the ring
what the six new readers read.  The bands belong to the cell's own size
and are left out here; the ``one-pass`` control's readings at that size
are in PERF.md section 2.  One small job; like test_readapt_job.py the
file lets go of what it compiled."""
import copy
import json
import os

import pytest

import traffic as trafficmod
from byname import load

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
with open(os.path.join(BENCH, "configs", "cube-shock-iso-scale6.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "traffic", "fresh-jobs.json")) as f:
    TRAFFIC = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
BANDS = ("ntets", "len_ok_share")
NEW = ["block_row_ms", "seam_share", "junction_verts", "cap_headroom",
       "displaced_share", "group_imbalance"]


def small(config):
    config = copy.deepcopy(config)
    config["mesh"]["args"]["n"] = 6
    config["mesh"]["jitter"] = 0.05 / 6
    config["metric"]["args"]["h"] = 0.8
    config["options"]["iparam"]["meshSize"] = 216
    for name in BANDS:
        del config["guarantees"][name]
    return config


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_the_configuration_is_cube_shock_isos_but_for_what_is_listed():
    with open(os.path.join(BENCH, "configs", "cube-shock-iso.json")) as f:
        iso = json.load(f)
    for key in ("domain", "kept"):
        assert CONFIG[key] == iso[key], key
    assert CONFIG["mesh"] == {"generator": "cube", "args": {"n": 24},
                              "jitter": 0.0020833}
    assert abs(CONFIG["mesh"]["jitter"] - 1 / (20 * 24)) < 1e-7
    assert CONFIG["metric"] == {"kind": "iso_shock", "args": {"h": 0.1333}}
    assert abs(CONFIG["metric"]["args"]["h"] - 3.2 / 24) < 1e-4
    assert CONFIG["options"]["dparam"] == iso["options"]["dparam"]
    assert CONFIG["options"]["iparam"] == dict(
        iso["options"]["iparam"], contiguousMode=0)
    for name, limit in iso["guarantees"].items():
        if name not in BANDS:
            assert CONFIG["guarantees"][name] == limit, name
    # no band is looser than cube-shock-iso's: the share of edges in
    # points, the tet count as a share of its middle
    lo, hi = CONFIG["guarantees"]["len_ok_share"]["band"]
    ilo, ihi = iso["guarantees"]["len_ok_share"]["band"]
    assert hi - lo <= ihi - ilo
    lo, hi = CONFIG["guarantees"]["ntets"]["band"]
    ilo, ihi = iso["guarantees"]["ntets"]["band"]
    assert (hi - lo) / (hi + lo) <= (ihi - ilo) / (ihi + ilo)
    assert set(CONFIG["reduced"]) == {"mesh"}
    assert any("contiguousMode" in a for a in CONFIG["assumed"])
    assert len(CONFIG["source"]) <= 200


def test_the_cell_and_its_six_metrics_are_in_the_benchmark():
    cell, = [w for w in BENCHMARK["workloads"] if w["name"] == "iso-scale6"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cube-shock-iso-scale6", "fresh-jobs", 1)
    trafficmod.validate(TRAFFIC)
    assert TRAFFIC["input"] == "fresh"
    entry, = [c for c in BENCHMARK["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == CONFIG["source"]
    # by name: where an entry stands in the list, and which other cells
    # a list names, is the next PR's to change
    metrics = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW:
        assert "iso-scale6" in metrics[name]["workloads"], name
        assert metrics[name]["moves"] == "job_s"
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py")), name


def test_one_job_fills_a_window_of_any_length():
    """The window's rule: the first job always starts and no other that
    cannot end inside the window, so a job of 35 s of 51 is alone in it,
    and so would one of 60 s be."""
    assert trafficmod.may_start(0, 51.0, 0.0)
    assert not trafficmod.may_start(1, 51.0 - 35.0, 35.0)
    assert not trafficmod.may_start(1, 51.0 - 60.0, 60.0)


def test_the_small_cell_is_correct_and_the_readers_read_it():
    pytest.importorskip("jax")
    import run as harness
    config = small(CONFIG)
    run_job = harness.job_runner(config)
    inp = trafficmod.job_input(config, TRAFFIC, 2147483659, run_job)
    assert len(inp["tet"]) == 1296
    out = run_job(inp)
    out["label"] = "small scale6"
    assert harness.judge_job(out, config)["ok"], out["numbers"]
    assert out["numbers"]["unmatched_interior_faces"] == 0
    assert len(out["tet"]) > len(inp["tet"])
    from span_fields import last_job_spans
    splits = last_job_spans("grp split")
    assert [s["groups"] for s in splits] == [6, 6]
    # at this size the displaced split outgrows the kept capacity and
    # takes the next rungs (tests/test_scale6_grouped.py); no regrow
    assert not last_job_spans("grp regrow")
    assert splits[0]["junction_verts"] > 0 and splits[0]["pieces"] > 6
    run = {"setup_s": 1.0, "jobs": [out], "chips": 1, "trace": None,
           "peaks": None, "window_compiles": 0}
    got = {name: load("layer_metrics", name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    assert got["junction_verts"] == splits[0]["junction_verts"]
    assert got["seam_share"] == pytest.approx(
        100.0 * splits[0]["seam_verts"] / splits[0]["verts"])
    assert got["cap_headroom"] == splits[-1]["headroom"] >= 0.0
    assert 0.0 < got["displaced_share"] < 100.0
    assert got["group_imbalance"] >= 0.0
    c = out["counters"]
    assert c["groups.rows"] == 6 * c["groups.dispatches"]
    assert got["block_row_ms"] == pytest.approx(
        1e3 * c["groups.pipeline.compute_s"] / c["groups.rows"])
