"""The torus cell's own job on the CPU at its own size, sound and with
its second pass left out (``control.py``).  Two jobs of about a minute
each, in a file the runner reaches after the cubes' and the sphere's:
like test_sphere_job.py it lets go of what it compiled."""
import json
import os

import pytest

import checker
import control
from byname import load
from inputs import build_input

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "torus-shock-aniso.json")) as f:
    CONFIG = json.load(f)
DOMAIN = CONFIG["domain"]
torus = load("domains", "torus")


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    import jax
    jax.clear_caches()
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


def test_the_sound_job_is_correct_and_one_pass_is_not():
    """The sound job meets every limit, its surface within the hausd it
    was given; stopped after the first pass it fails the band that is
    there for it, and nothing else."""
    pytest.importorskip("jax")
    inp = build_input(CONFIG, 2147483659)
    failed, numbers, out = job_verdict(CONFIG, inp)
    assert failed == set(), (failed, numbers)
    uniq, cnt = checker.face_counts(out["tet"])
    vertex, chord = torus.deviations(out["vert"][uniq[cnt == 1]], DOMAIN)
    assert chord.max() <= CONFIG["options"]["dparam"]["hausd"]
    assert vertex.max() <= DOMAIN["vertex_tol"]
    c = out["counters"]
    # the curvature's tensor changed every regular surface vertex's, and
    # the hausd test then refuses a collapse in a hundred, not two in five
    assert c["surf.bound_verts"] == c["surf.bdy_verts"] == 1920
    assert c["surf.hveto"] < 0.05 * c["adapt.ncollapse"]
    assert c["surf.bmoved"] > 1000 and c["surf.bsplit"] > 50
    assert "hausd bound" in out["phases"]
    one_pass = control.apply("one-pass", CONFIG)
    assert CONFIG["options"]["iparam"]["niter"] == 2    # a copy was changed
    failed, numbers, _ = job_verdict(one_pass, inp)
    assert failed == {"ntets"}, (failed, numbers)
