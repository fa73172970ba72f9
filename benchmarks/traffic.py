"""The one traffic generator: a mix is a data file (``traffic/<name>.json``).

``loop``    closed (a client sends its next job when the last came back)
``clients`` callers in the loop; the library is one caller deep, so 1
``unit``    what one request is: a whole adaptation ("job")
``input``   ``fresh``: every job adapts the coarse seeded input;
            ``readapt``: every job adapts the OUTPUT of one growth job
            (made in set-up) to the configuration's metric moved by
            ``delta`` along x — a solver following a moving front

and the window's rule, which a closed loop of whole jobs needs because a
job is a large part of the window.
"""
from __future__ import annotations

from inputs import build_input, metric_at


def validate(traffic: dict) -> None:
    if (traffic.get("loop"), traffic.get("clients"),
            traffic.get("unit")) != ("closed", 1, "job"):
        raise ValueError("this generator drives a closed loop of one client "
                         f"sending whole jobs; the mix asks for {traffic}")
    if traffic.get("input") not in ("fresh", "readapt"):
        raise ValueError(f"unknown input state {traffic.get('input')!r}")


def job_input(config: dict, traffic: dict, seed: int, run_job) -> dict:
    """The arrays every job of the window is staged from.  ``run_job``
    runs one adaptation (needed by ``readapt`` only)."""
    validate(traffic)
    inp = build_input(config, seed)
    if traffic["input"] == "fresh":
        return inp
    grown = run_job(inp)
    if grown["rc"] != 0:
        raise RuntimeError("the growth job that readapt starts from failed")
    return {"vert": grown["vert"], "tet": grown["tet"],
            "met": metric_at(config["metric"], grown["vert"],
                             shift=traffic["delta"])}


def may_start(jobs_done: int, remaining_s: float, last_job_s: float) -> bool:
    """The window's rule.  The first job always starts, so no window is
    empty; a later one starts only while what is left of the window is
    at least what the previous job took, so none is begun that cannot
    end inside it (jobs on one input take the same time)."""
    return jobs_done == 0 or remaining_s >= last_job_s


def closed_loop(run_one, seconds: float, clock) -> list[dict]:
    """Run jobs back to back for ``seconds``; each result gets the time
    it ended, counted from the window's start (``end_s``)."""
    t0 = clock()
    done: list[dict] = []
    last = 0.0
    while may_start(len(done), seconds - (clock() - t0), last):
        res = run_one(len(done))
        res["end_s"] = clock() - t0
        last = res["seconds"]
        done.append(res)
    return done
