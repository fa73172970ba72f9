"""The window's rule and the closed loop that follows it."""
import pytest

import traffic


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def loop(job_seconds, window):
    clock = Clock()

    def run_one(i):
        clock.t += job_seconds[min(i, len(job_seconds) - 1)]
        return {"seconds": job_seconds[min(i, len(job_seconds) - 1)]}
    return traffic.closed_loop(run_one, window, clock)


@pytest.mark.parametrize("job_s,window,expect", [
    (35.0, 51.0, 1),        # a second job could not end inside
    (17.0, 51.0, 3),        # 17 + 17 + 17 = 51: the third still fits
    (17.1, 51.0, 2),
    (80.0, 51.0, 1),        # never zero jobs, even when one overruns
    (0.5, 51.0, 102),
])
def test_jobs_in_a_window(job_s, window, expect):
    jobs = loop([job_s], window)
    assert len(jobs) == expect
    # no job was started that could not end inside, bar the first
    assert all(j["end_s"] <= window + 1e-9 for j in jobs[1:])


def test_the_rule_uses_the_previous_jobs_duration():
    jobs = loop([10.0, 30.0, 30.0], 51.0)       # after 40 s, 11 are left
    assert [j["end_s"] for j in jobs] == [10.0, 40.0]


def test_may_start():
    assert traffic.may_start(0, 0.0, 99.0)
    assert traffic.may_start(1, 20.0, 20.0)
    assert not traffic.may_start(1, 19.9, 20.0)


@pytest.mark.parametrize("bad", [
    {"loop": "open", "clients": 1, "unit": "job", "input": "fresh"},
    {"loop": "closed", "clients": 4, "unit": "job", "input": "fresh"},
    {"loop": "closed", "clients": 1, "unit": "job", "input": "stale"},
])
def test_a_mix_the_generator_cannot_drive_is_refused(bad):
    with pytest.raises(ValueError):
        traffic.validate(bad)


def test_readapt_starts_from_a_growth_jobs_output_under_a_moved_metric():
    import numpy as np
    from inputs import build_input, metric_at
    config = {"mesh": {"generator": "cube", "args": {"n": 3},
                       "jitter": 0.01},
              "metric": {"kind": "iso_shock", "args": {"h": 0.2}}}
    fresh = build_input(config, 3)
    calls = []

    def growth_job(inp):
        calls.append(inp)
        return {"rc": 0, "vert": inp["vert"] + 0.001, "tet": inp["tet"]}
    mix = {"loop": "closed", "clients": 1, "unit": "job",
           "input": "readapt", "delta": 0.05}
    inp = traffic.job_input(config, mix, 3, growth_job)
    assert len(calls) == 1 and np.array_equal(calls[0]["vert"], fresh["vert"])
    assert np.array_equal(inp["vert"], fresh["vert"] + 0.001)
    # the plane sits at x = 0.55 now: sizes are smallest there
    assert np.allclose(inp["met"], metric_at(config["metric"], inp["vert"],
                                             shift=0.05))
    assert np.isclose(inp["met"].min(), 0.2 * (0.2 + 4 * np.abs(
        inp["vert"][:, 0] - 0.55).min()))
    # the same seed gives the same input; fresh traffic runs no job
    again = traffic.job_input(config, dict(mix, input="fresh"), 3, None)
    assert np.array_equal(again["vert"], fresh["vert"])


def test_a_failed_growth_job_stops_readapt():
    config = {"mesh": {"generator": "cube", "args": {"n": 2}, "jitter": 0.0},
              "metric": {"kind": "iso_shock", "args": {"h": 0.2}}}
    mix = {"loop": "closed", "clients": 1, "unit": "job",
           "input": "readapt", "delta": 0.05}
    with pytest.raises(RuntimeError):
        traffic.job_input(config, mix, 1, lambda inp: {"rc": 1})
