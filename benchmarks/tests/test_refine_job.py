"""The cell ``iso-refine``'s job on the CPU at a small size (the cube at
``n`` 6 in six groups of ``meshSize`` 216 under the size map at 2.5/n,
the cell's own growth at that lattice; tests/test_refine_grouped.py has
the program's side): the configuration is ``cube-shock-iso-scale6``'s
but for what issue 47 lists (at the cut it wrote for a job over 120 s,
``cube-shock-iso``'s mesh), a job staged as ``run.py`` stages it meets
every exact guarantee, re-cuts inside its first pass and between the
passes on ONE block program without a regrow, and leaves in the ring and
the counters what the six new readers read.  The bands belong to the
cell's own size and are left out here; the ``one-pass`` control's
readings at that size are in PERF.md section 2.  One small job; like
test_scale6_job.py the file lets go of what it compiled."""
import copy
import json
import os

import pytest

import traffic as trafficmod
from byname import load

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
with open(os.path.join(BENCH, "configs", "cube-shock-iso-refine.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "configs", "cube-shock-iso-scale6.json")) as f:
    SCALE6 = json.load(f)
with open(os.path.join(BENCH, "configs", "cube-shock-iso.json")) as f:
    ISO = json.load(f)
with open(os.path.join(BENCH, "traffic", "fresh-jobs.json")) as f:
    TRAFFIC = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
BANDS = ("ntets", "len_ok_share")
NEW = {"recuts": ["iso-refine"], "recut_s": ["iso-refine"],
       "groups_peak": ["iso-refine"], "tile_fill": ["iso-refine"]}
ONE_CHIP = ["iso-growth", "aniso-coarsen", "sphere-growth", "torus-coarsen",
            "iso-readapt", "iso-scale6", "iso-refine"]
NEW.update(split_share=ONE_CHIP, growth=ONE_CHIP)


def small(config):
    config = copy.deepcopy(config)
    config["mesh"]["args"]["n"] = 6
    config["mesh"]["jitter"] = 0.05 / 6
    config["metric"]["args"]["h"] = 2.5 / 6
    config["options"]["iparam"]["meshSize"] = 216
    # the ceiling at this size: the rung three groups of 216 take
    config["options"]["iparam"]["groupCapacity"] = 746
    for name in BANDS:
        del config["guarantees"][name]
    return config


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_the_configuration_is_scale6s_but_for_what_is_listed():
    for key in ("domain", "kept"):
        assert CONFIG[key] == SCALE6[key], key
    # the written cut: a warm job at n 24 took 161.9 s on the chip, over
    # the issue's 120, so the mesh is cube-shock-iso's (n 16) and the
    # map's scale 1.5 / 16
    assert CONFIG["mesh"] == ISO["mesh"]
    assert CONFIG["metric"] == {"kind": "iso_shock", "args": {"h": 0.09375}}
    assert CONFIG["metric"]["args"]["h"] == 1.5 / 16
    assert CONFIG["options"]["dparam"] == SCALE6["options"]["dparam"]
    # the one name the parent's API lacks: the ceiling the re-cut works
    # under, the rung the first cut takes anyway
    assert CONFIG["options"]["iparam"] == dict(
        SCALE6["options"]["iparam"], groupCapacity=43118)
    for name, limit in SCALE6["guarantees"].items():
        if name not in BANDS:
            assert CONFIG["guarantees"][name] == limit, name
    assert set(CONFIG["guarantees"]) == set(SCALE6["guarantees"])
    # no band is looser than the one the same mesh has in its own cell
    # (cube-shock-iso's): the share of edges in points, the tet count as
    # a share of its middle
    lo, hi = CONFIG["guarantees"]["len_ok_share"]["band"]
    slo, shi = ISO["guarantees"]["len_ok_share"]["band"]
    assert hi - lo <= shi - slo
    lo, hi = CONFIG["guarantees"]["ntets"]["band"]
    slo, shi = ISO["guarantees"]["ntets"]["band"]
    assert (hi - lo) / (hi + lo) <= (shi - slo) / (shi + slo)
    # the output is 4.5x-5.4x the input
    assert 4.5 * 24576 < lo < hi < 5.4 * 24576
    assert set(CONFIG["reduced"]) == {"mesh"}
    assert len(CONFIG["source"]) <= 200
    told = " ".join(CONFIG["assumed"])
    assert "bench.py" in told and "contiguousMode" in told
    assert "would grow it 4.8x" not in told


def test_the_cell_and_its_six_metrics_are_in_the_benchmark():
    cell, = [w for w in BENCHMARK["workloads"] if w["name"] == "iso-refine"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cube-shock-iso-refine", "fresh-jobs", 1)
    trafficmod.validate(TRAFFIC)
    entry, = [c for c in BENCHMARK["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == ["mesh"]
    metrics = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name, cells in NEW.items():
        assert metrics[name]["workloads"] == cells, name
        assert metrics[name]["moves"] == "job_s"
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py")), name
    # wherever iso-scale6 reports a grouped metric, so does this cell
    for m in BENCHMARK["per_layer"]:
        if "iso-scale6" in m.get("workloads", ()):
            assert "iso-refine" in m["workloads"], m["name"]
    assert BENCHMARK["workloads"][-1] is cell       # added at the end
    assert [m["name"] for m in BENCHMARK["per_layer"][-6:]] == list(NEW)


def test_one_job_fills_a_window_of_any_length():
    """The window's rule: the first job always starts, and a job of 90 s
    is alone in a window of 51."""
    assert trafficmod.may_start(0, 51.0, 0.0)
    assert not trafficmod.may_start(1, 51.0 - 90.0, 90.0)


def test_the_small_cell_is_correct_and_the_readers_read_it():
    pytest.importorskip("jax")
    import run as harness
    config = small(CONFIG)
    run_job = harness.job_runner(config)
    inp = trafficmod.job_input(config, TRAFFIC, 2147483659, run_job)
    assert len(inp["tet"]) == 1296
    out = run_job(inp)
    out["label"] = "small refine"
    assert harness.judge_job(out, config)["ok"], out["numbers"]
    assert out["numbers"]["unmatched_interior_faces"] == 0
    assert out["numbers"]["qmin"] >= 0.001
    assert len(out["tet"]) > 4 * len(inp["tet"])
    from span_fields import last_job_spans
    splits = last_job_spans("grp split")
    recuts = last_job_spans("grp recut")
    assert splits[0]["groups"] == 6 and len(splits) >= 3
    assert {r["why"] for r in recuts} == {"overflow", "pass"}
    assert not last_job_spans("grp regrow")
    assert {s["capT"] for s in splits} == {746}
    c = out["counters"]
    assert c["compile.block_programs"] == 1
    assert c.get("groups.regrows", 0) == 0
    run = {"setup_s": 1.0, "jobs": [out], "chips": 1, "trace": None,
           "peaks": None, "window_compiles": 0}
    got = {name: load("layer_metrics", name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    assert got["recuts"] == len(recuts) == c["groups.recuts"] >= 2
    assert got["recut_s"] == pytest.approx(sum(r["dur"] for r in recuts))
    assert got["groups_peak"] == max(s["groups"] for s in splits) > 12
    assert got["tile_fill"] == pytest.approx(
        100.0 * (1.0 - c["groups.rows_dead"] / c["groups.rows"]))
    assert 50.0 < got["tile_fill"] < 100.0
    assert 50.0 < got["split_share"] < 100.0        # a job that refines
    assert got["growth"] == pytest.approx(len(out["tet"]) / 1296)
    blocks = last_job_spans("grp block")
    assert c["groups.rows"] == sum(b["rows"] for b in blocks) \
        == 6 * c["groups.dispatches"]
