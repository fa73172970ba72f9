"""The accepted reducer on a capture that holds PROGRAM spans, recorded
on a v5e with record_span_trace.py: two toy blocks under ``grp block``
spans, a host nap under ``grp merge`` between them and two under ``polish
wave`` after, all inside the benchmark's ``bench.job`` / ``bench.run``."""
import json
import os

import pytest

import trace_reduce as tr
from byname import load

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "span_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    with open(DATA + ".spans.json") as f:
        job = json.load(f)
    profile = tr.load(DATA)
    return profile, tr.reduce_profile(
        profile, [tuple(s) for s in job["spans"]], job["t_epoch"],
        block_module=tr.re.compile(r"^jit_block\b"))


def test_the_capture_holds_the_program_spans(reduced):
    profile, _ = reduced
    names = {h["name"] for h in tr.host_events(profile)}
    assert {"bench.job", "bench.run", "run", "adaptation", "grp block",
            "grp merge", "bad-element polish", "polish wave"} <= names


def test_idle_gaps_are_named_by_program_spans(reduced):
    _, red = reduced
    assert red["blocks"] == 2
    gaps = dict(red["breakdown"]["idle_gaps"])
    # a key is "<the job's shortest span round the gap's middle>: <the
    # shortest host event over at least half of it>".  The nap between
    # the blocks lies in one span; the two naps after them are ONE gap of
    # the device, so its label is the span round both waves
    assert gaps["grp merge: grp merge"] == pytest.approx(0.05, abs=0.01)
    assert gaps["polish wave: bad-element polish"] \
        == pytest.approx(0.04, abs=0.01)
    assert not any(k.endswith("bench.run") for k in gaps)
    run = {"trace": red}
    assert load("layer_metrics", "idle_named_share").read(run) >= 90.0
