"""BENCHMARK.json against the contract's letter, and against the files it
names: a file outside these limits is refused before a single run."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert len(BENCH["command"]) <= 32 and all(map(line, BENCH["command"]))
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # the whole check has to fit with the full 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_configs():
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        # every reduced key names a top-level group of the file, whose
        # `reduced` says why
        assert set(c["reduced"]) == set(body["reduced"])
        assert all(k in body for k in c["reduced"])
        for key in ("source", "mesh", "metric", "options", "domain",
                    "guarantees", "assumed"):
            assert key in body, key
        # whatever a configuration names has its file
        for kind, name in (("meshes", body["mesh"]["generator"]),
                           ("metrics", body["metric"]["kind"]),
                           ("domains", body["domain"]["kind"])):
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", kind, name + ".py")), (kind, name)
        assert all("reason" in g and "PLACEHOLDER" not in g["reason"]
                   for g in body["guarantees"].values())
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_workloads():
    names = [c["name"] for c in BENCH["configs"]]
    seen = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert line(w["why"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "end_to_end", m["name"] + ".py"))
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert line(m["layer"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    every = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells


@pytest.mark.parametrize("kind,reader", sorted(
    (kind, f[:-3]) for kind in ("end_to_end", "layer_metrics")
    for f in os.listdir(os.path.join(ROOT, "benchmarks", kind))
    if f.endswith(".py")))
def test_every_reader_is_named_in_the_benchmark(kind, reader):
    key = "per_layer" if kind == "layer_metrics" else kind
    assert reader in {m["name"] for m in BENCH[key]}
