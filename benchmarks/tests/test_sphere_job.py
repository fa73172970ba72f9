"""The sphere cell's own job on the CPU at its own size, sound and as its
controls: a pass left out (``control.py``), and the Bezier lift switched
off (``-nr``, built here from the configuration's dict).  Three jobs of
about a minute and a half each, in a file the runner reaches after the
cubes' (test_control.py): a process that has compiled a thousand XLA:CPU
programs more is the one in which this image's compiler gives out, so
the file also lets go of what it compiled."""
import copy
import json
import os

import pytest

import checker
import control
from byname import load
from inputs import build_input

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "sphere-sizemap-iso.json")) as f:
    CONFIG = json.load(f)
DOMAIN = CONFIG["domain"]
ball = load("domains", "ball")


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    import jax
    jax.clear_caches()          # the cubes' programs, before; ours, after
    yield
    jax.clear_caches()


def job_verdict(config, inp):
    import run as harness
    out = harness.job_runner(config)(inp)
    numbers = checker.measure(out["vert"], out["tet"], out["met"],
                              config["domain"])
    numbers["degraded"] = int(out["rc"] != 0)
    return {r["name"] for r in checker.judge(numbers, config["guarantees"])
            if not r["ok"]}, numbers, out


@pytest.fixture(scope="module")
def cell_input():
    return build_input(CONFIG, 2147483659)


def test_the_sound_job_is_correct_and_one_pass_is_not(cell_input):
    """About three minutes on a CPU.  The sound job meets every limit;
    stopped after the first pass it fails a band that is there for it,
    and nothing else."""
    pytest.importorskip("jax")
    failed, numbers, out = job_verdict(CONFIG, cell_input)
    assert failed == set(), (failed, numbers)
    # a fifth of the splits are of boundary edges: the lifted ones
    c = out["counters"]
    assert c["surf.bsplit"] > 0.15 * c["adapt.nsplit"]
    one_pass = control.apply("one-pass", CONFIG)
    assert CONFIG["options"]["iparam"]["niter"] == 2    # a copy was changed
    failed, numbers, _ = job_verdict(one_pass, cell_input)
    assert failed and failed <= {"ntets", "len_ok_share"}, (failed, numbers)


def test_the_job_without_the_lift_is_not_correct(cell_input):
    """``-nr`` (``IParam.angle`` 0): no ridge detection, so the driver
    drops ``hausd`` and every surface midpoint stays on its chord.  The
    output conforms and is no ball: by the domain's tolerances its skin
    is not on the sphere."""
    pytest.importorskip("jax")
    no_lift = copy.deepcopy(CONFIG)
    no_lift["options"]["iparam"]["angle"] = 0
    failed, numbers, out = job_verdict(no_lift, cell_input)
    assert "unmatched_interior_faces" in failed, (failed, numbers)
    assert numbers["inverted_tets"] == 0 and numbers["overfull_faces"] == 0
    uniq, cnt = checker.face_counts(out["tet"])
    vertex, chord = ball.deviations(out["vert"][uniq[cnt == 1]], DOMAIN)
    # both tolerances tell it from a sound job on their own
    assert vertex.max() > 2 * DOMAIN["vertex_tol"]
    assert chord.max() > DOMAIN["chord_tol"]
