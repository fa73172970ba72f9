"""The rest of a run, past the look for a chip, with the timed path
broken underneath: ``correct`` must come out false.  The job is a stand-in
that hands back the (conforming) input mesh, so no program is imported."""
import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

import run as harness
from test_checker import GUARANTEES, SMALL, bf16

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELL = next(c for c in BENCH["workloads"] if c["name"] == "iso-growth")
CONFIG = dict(SMALL, guarantees=GUARANTEES)
TRAFFIC = {"loop": "closed", "clients": 1, "unit": "job", "input": "fresh"}


def sound_job(inp, annotate=None):
    time.sleep(0.01)
    return {"rc": 0, "seconds": 0.01, "t_epoch": time.time(),
            "vert": inp["vert"].astype(np.float32).astype(np.float64),
            "tet": inp["tet"].copy(), "met": inp["met"].copy(),
            "phases": {"analysis": 0.001, "metric": 0.001,
                       "adaptation": 0.006, "bad-element polish": 0.002},
            "spans": [("adaptation/grp compute", 0.0, 0.0)] * 2,
            "counters": {"groups.dispatches": 24.0,
                         "groups.pipeline.compute_s": 0.004,
                         "resilience.retries": 0.0}}


def broken(kind):
    calls = {"n": 0}

    def job(inp, annotate=None):
        out = sound_job(inp)
        calls["n"] += 1
        if calls["n"] != 2:         # the first job of the window only
            return out
        if kind == "lowfailure":
            out["rc"] = 1
        elif kind == "resilience":
            out["counters"]["resilience.retries"] = 1.0
        elif kind == "dropped_tet":
            inner = np.all((out["vert"][out["tet"]] > 0.2)
                           & (out["vert"][out["tet"]] < 0.8), axis=(1, 2))
            out["tet"] = np.delete(out["tet"], np.where(inner)[0][0], 0)
        elif kind == "unchanged_state":
            # a step that returns nothing new: no vertex was placed
            out["vert"] = bf16(out["vert"])
        return out
    return job


def measure(job, trace=0, seconds=0.08):
    args = SimpleNamespace(seed=7, seconds=seconds, trace=trace)
    compiles = {"n": 3, "s": 1.0, "names": ["a", "b", "c"],
                "cache_hits": 2}
    return harness.measure_cell(BENCH, CELL, CONFIG, TRAFFIC, args, job,
                                {"platform": "none"}, compiles)


def test_a_sound_run_is_correct_and_reports_every_end_to_end_metric():
    res = measure(sound_job)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    jobs_s = res["metrics"]["job_s"]["value"]
    assert 0.009 < jobs_s < 0.05


@pytest.mark.parametrize("kind", ["lowfailure", "resilience", "dropped_tet",
                                  "unchanged_state"])
def test_a_broken_job_makes_the_run_incorrect(kind):
    res = measure(broken(kind))
    assert res["correct"] is False
    assert res["failed"] == 1 and res["attempted"] >= 2


def test_a_traced_rehearsal_reports_the_host_side_layers_only():
    res = measure(sound_job, trace=1)
    assert res["correct"]
    host_side = {"stage_s", "window_compiles", "pass_s", "split_merge_s",
                 "block_ms", "tail_s", "qmean", "len_ok_share"}
    assert set(res["metrics"]) == host_side
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert res["metrics"]["pass_s"]["value"] == pytest.approx(0.003)
    assert "busy_s" not in res["device"] and "breakdown" not in res
