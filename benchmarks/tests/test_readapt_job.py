"""The re-adaptation cell's job on the CPU at a small size (the cube at
``n`` 6 in three groups of ``meshSize`` 640, as tests/test_readapt_grouped.py
has it): what ``traffic.job_input`` hands the window under the mix
``readapt`` IS the growth job's output under the shock moved by the
mix's ``delta``, and a job staged from it meets every exact guarantee of
the configuration.  The bands (``ntets``, ``len_ok_share``) belong to
the cell's own size and are left out here, so the ``one-pass`` control
cannot be told from a sound job at this size: its readings at the cell's
size are in PERF.md section 2.  Three small jobs; like test_torus_job.py
the file lets go of what it compiled."""
import copy
import json
import os

import numpy as np
import pytest

import checker
import traffic as trafficmod
from byname import load
from inputs import build_input

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "cube-shock-iso-readapt.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "traffic", "readapt.json")) as f:
    TRAFFIC = json.load(f)
BANDS = ("ntets", "len_ok_share")


def small(config):
    config = copy.deepcopy(config)
    config["mesh"]["args"]["n"] = 6
    config["mesh"]["jitter"] = 0.05 / 6
    config["metric"]["args"]["h"] = 0.8
    config["options"]["iparam"]["meshSize"] = 640
    for name in BANDS:
        del config["guarantees"][name]
    return config


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_the_mix_and_the_configuration_are_the_issues():
    trafficmod.validate(TRAFFIC)
    assert TRAFFIC["input"] == "readapt" and TRAFFIC["delta"] == 0.05
    with open(os.path.join(BENCH, "configs", "cube-shock-iso.json")) as f:
        iso = json.load(f)
    # cube-shock-iso's mesh, options, domain and exact guarantees; its
    # metric at another h, and the cut's request stated (the default):
    # a program whose API lacks the name cannot stage the configuration
    for key in ("mesh", "domain", "kept"):
        assert CONFIG[key] == iso[key], key
    assert CONFIG["options"]["dparam"] == iso["options"]["dparam"]
    assert CONFIG["options"]["iparam"] == dict(
        iso["options"]["iparam"], contiguousMode=0)
    assert any("contiguousMode" in a for a in CONFIG["assumed"])
    assert CONFIG["metric"] == {"kind": "iso_shock", "args": {"h": 0.18}}
    for name, limit in iso["guarantees"].items():
        if name not in BANDS:
            assert CONFIG["guarantees"][name] == limit, name
    assert any("0.18" in a for a in CONFIG["assumed"])


def test_the_windows_input_is_the_growth_jobs_output_under_the_moved_shock():
    """No job runs: a stand-in ``run_job`` hands back arrays of its own,
    and the mix's input is those arrays, not copies, with the
    configuration's metric at ``shift`` = ``delta`` on its vertices."""
    config = small(CONFIG)
    seen = []
    rng = np.random.default_rng(5)
    grown = {"rc": 0, "vert": rng.uniform(0.0, 1.0, (40, 3)),
             "tet": rng.integers(0, 40, (90, 4)), "met": np.ones(40)}

    def run_job(inp):
        seen.append(inp)
        return grown
    inp = trafficmod.job_input(config, TRAFFIC, 7, run_job)
    assert len(seen) == 1       # ONE growth job, from the seeded lattice
    fresh = build_input(config, 7)
    assert all((seen[0][k] == fresh[k]).all() for k in fresh)
    assert inp["vert"] is grown["vert"] and inp["tet"] is grown["tet"]
    moved = load("metrics", "iso_shock").at(grown["vert"], shift=0.05,
                                            h=0.8)
    assert (inp["met"] == moved).all()
    assert not (inp["met"] == load("metrics", "iso_shock").at(
        grown["vert"], h=0.8)).all()
    grown["rc"] = 1
    with pytest.raises(RuntimeError):
        trafficmod.job_input(config, TRAFFIC, 7, run_job)


def test_the_small_cell_is_correct_on_a_sound_seed():
    """The timed path at a small size: the growth job in set-up, then a
    job of the window, judged as run.py judges it."""
    pytest.importorskip("jax")
    import run as harness
    config = small(CONFIG)
    run_job = harness.job_runner(config)
    grown = []

    def growth_job(inp):
        grown.append(run_job(inp))
        return grown[-1]
    inp = trafficmod.job_input(config, TRAFFIC, 2147483659, growth_job)
    assert inp["vert"] is grown[0]["vert"] and inp["tet"] is grown[0]["tet"]
    out = run_job(inp)
    out["label"] = "small readapt"
    assert harness.judge_job(out, config)["ok"], out["numbers"]
    assert out["numbers"]["unmatched_interior_faces"] == 0
    # three groups in both passes: a middle group on two seams
    from span_fields import last_job_spans
    assert [s["groups"] for s in last_job_spans("grp split")] == [3, 3]
    # behind the moved front the job coarsens
    c = out["counters"]
    assert c["adapt.ncollapse"] > c["adapt.nsplit"]
    assert len(out["tet"]) < len(inp["tet"])
    assert "groups.cond_skipped" in c
    # the same job with its second pass left out meets the exact
    # guarantees too: only the bands of the cell's own size refuse it
    import control
    one_pass = control.apply("one-pass", config)
    out = harness.job_runner(one_pass)(inp)
    out["label"] = "small readapt, one pass"
    assert harness.judge_job(out, one_pass)["ok"], out["numbers"]
