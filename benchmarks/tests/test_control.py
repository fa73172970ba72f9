"""The controls (control.py) on the CPU: the program itself, with a pass
left out, comes out NOT correct; the bfloat16 patch reaches the
program's length and quality functions."""
import json
import os

import numpy as np
import pytest

import checker
import control
from inputs import build_input

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def verdict(config, inp):
    import run as harness
    out = harness.job_runner(config)(inp)
    numbers = checker.measure(out["vert"], out["tet"], out["met"],
                              config["domain"])
    numbers["degraded"] = int(out["rc"] != 0)
    return {r["name"] for r in checker.judge(numbers, config["guarantees"])
            if not r["ok"]}


@pytest.mark.parametrize("name", ["cube-shock-iso", "cube-shock-aniso"])
def test_a_job_with_a_pass_left_out_is_not_correct(name):
    """At the cell's own size and under its own limits (a CPU holds it:
    about two minutes a cell): the sound job passes, the same job
    stopped after the first pass fails the number that is there for it."""
    pytest.importorskip("jax")
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    inp = build_input(config, 2147483659)
    assert verdict(config, inp) == set()
    one_pass = control.apply("one-pass", config)
    assert one_pass["options"]["iparam"]["niter"] == 1
    assert config["options"]["iparam"]["niter"] == 2    # a copy was changed
    failed = verdict(one_pass, inp)
    assert failed and failed <= {"ntets", "len_ok_share"}, failed


def test_the_bfloat16_control_reaches_the_programs_arithmetic():
    pytest.importorskip("jax")
    from parmmg_tpu.ops import quality
    sound = quality.edge_length_iso
    rng = np.random.default_rng(3)
    p0, p1 = rng.random((2, 4096, 3)).astype(np.float32)
    h0, h1 = (0.1 + rng.random((2, 4096))).astype(np.float32)
    exact = np.asarray(sound(p0, p1, h0, h1))
    assert control.low_precision() >= 6
    try:
        low = np.asarray(quality.edge_length_iso(p0, p1, h0, h1))
        rel = np.abs(low - exact) / exact
        # eight bits of significand, not twenty-four
        assert 1e-3 < np.median(rel) < 2e-2
        assert low.dtype == np.float32
    finally:                # leave the program as it was found
        for modname, names in control.LOW_PRECISION_TARGETS.items():
            mod = __import__(modname, fromlist=["x"])
            for name in names:
                setattr(mod, name, getattr(mod, name).__wrapped__)


def test_a_target_the_program_lost_is_an_error(monkeypatch):
    pytest.importorskip("jax")
    monkeypatch.setitem(control.LOW_PRECISION_TARGETS,
                        "parmmg_tpu.ops.quality", ("no_such_function",))
    with pytest.raises(AttributeError):
        control.low_precision()
