"""The eight readers of the SPMD loop (PR 41) on a hand-made ``run``: a
value where the job's counters and the program's ring hold their source,
None on a program that lacks them (the parent, or a job on the grouped
path); and the four-chip configuration's file against the checker's
keys and ``cube-shock-iso``'s, as test_readapt_job.py does for PR 37's.
The spans are made with the program's own primitive."""
import json
import os

import pytest

import checker
from byname import load
from test_layer_readers import grouped_job, job, run_of

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ["spmd_block_ms", "spmd_refresh_s", "spmd_migrate_s",
           "spmd_split_merge_s", "exchange_mb", "migrated_share",
           "shard_imbalance", "devices_active"]


def spmd_job(shift=0.0):
    t = 10.0 + shift
    spans = [
        ("analysis", t, t + 1.0), ("metric", t + 1.0, t + 1.5),
        ("dist split", t + 1.5, t + 3.5),
        ("dist block", t + 3.5, t + 3.75), ("dist block", t + 3.75, t + 4.0),
        ("dist refresh", t + 4.0, t + 4.5),
        ("dist displace", t + 4.5, t + 4.75),
        ("dist migrate", t + 4.75, t + 5.75),
        ("dist block", t + 5.75, t + 6.0),
        ("dist refresh", t + 6.0, t + 6.25),
        ("dist merge", t + 6.25, t + 7.25),
        ("adaptation", t + 1.5, t + 7.25), ("run", t, t + 12.0),
    ]
    return job(spans, {
        "dist.dispatches": 3.0, "dist.pipeline.compute_s": 0.75,
        "dist.exchange_bytes": 6.5e6, "dist.migrated_tets": 31382.0,
        "dist.live_tets": 100000.0, "dist.largest_shard": 15000.0,
        "dist.devices": 4.0})


@pytest.fixture
def ring():
    """The ring as the last job of a window leaves it."""
    from parmmg_tpu.obs import trace as otrace
    otrace.TRACER.reset()
    with otrace.span("run"):
        with otrace.span("dist split", shards=8, G=2) as sp:
            sp.set(capP=8516, capT=43118, largest=12119)
    yield otrace
    otrace.TRACER.reset()


@pytest.mark.parametrize("name,expect", [
    ("spmd_block_ms", 250.0),
    ("spmd_refresh_s", 0.75),
    ("spmd_migrate_s", 1.25),
    ("spmd_split_merge_s", 3.0),
    ("exchange_mb", 6.5),
    ("migrated_share", 31.382),
    ("shard_imbalance", 20.0),
    ("devices_active", 4.0),
])
def test_a_reader_finds_its_span_or_counter(ring, name, expect):
    run = run_of([spmd_job(), spmd_job(shift=20.0)])
    assert load("layer_metrics", name).read(run) == pytest.approx(expect)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_on_a_program_without_the_loops_counters(name):
    """The parent's job, or any job on the grouped path: no ``dist.*``
    counter, no ``dist *`` span, an empty ring."""
    from parmmg_tpu.obs import trace as otrace
    otrace.TRACER.reset()
    assert load("layer_metrics", name).read(
        run_of([grouped_job(), grouped_job(shift=7.0)])) is None


def test_zero_counters_are_values_and_an_empty_mesh_is_none(ring):
    j = spmd_job()
    j["counters"].update({"dist.migrated_tets": 0.0,
                          "dist.exchange_bytes": 0.0})
    run = run_of([j])
    assert load("layer_metrics", "migrated_share").read(run) == 0.0
    assert load("layer_metrics", "exchange_mb").read(run) == 0.0
    j["counters"]["dist.live_tets"] = 0.0
    assert load("layer_metrics", "migrated_share").read(run) is None
    assert load("layer_metrics", "shard_imbalance").read(run) is None


def test_the_imbalance_needs_the_split_spans_shards():
    from parmmg_tpu.obs import trace as otrace
    otrace.TRACER.reset()
    assert load("layer_metrics", "shard_imbalance").read(
        run_of([spmd_job()])) is None


def test_the_configuration_is_cube_shock_isos_on_four_ranks():
    with open(os.path.join(BENCH, "configs",
                           "cube-shock-iso-spmd4.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "cube-shock-iso.json")) as f:
        iso = json.load(f)
    assert cfg["mesh"] == {"generator": "cube", "args": {"n": 25},
                           "jitter": 0.002}
    assert cfg["metric"] == {"kind": "iso_shock", "args": {"h": 0.128}}
    # the same map on a finer lattice: h over the cell's size is iso's
    assert cfg["metric"]["args"]["h"] * 25 == pytest.approx(
        iso["metric"]["args"]["h"] * 16)
    assert cfg["options"]["iparam"] == dict(
        iso["options"]["iparam"], nDevices=4)
    assert cfg["options"]["dparam"] == iso["options"]["dparam"]
    assert cfg["domain"] == iso["domain"]
    assert list(cfg["reduced"]) == ["mesh"]
    # the exact guarantees to the letter; the bands are the cell's own
    bands = ("ntets", "len_ok_share")
    for name, limit in iso["guarantees"].items():
        if name not in bands:
            assert cfg["guarantees"][name] == limit, name
    assert set(cfg["guarantees"]) == set(iso["guarantees"])
    for name in bands:
        lo, hi = cfg["guarantees"][name]["band"]
        assert lo < hi
    lo, hi = cfg["guarantees"]["ntets"]["band"]
    assert lo > 93750          # a job that did not adapt fails the band


def test_the_checker_judges_every_guarantee_of_the_configuration():
    with open(os.path.join(BENCH, "configs",
                           "cube-shock-iso-spmd4.json")) as f:
        cfg = json.load(f)
    numbers = {"degraded": 0, "broken": 0, "inverted_tets": 0,
               "volume_rel_err": 0.0, "overfull_faces": 0,
               "unmatched_interior_faces": 0, "qmin": 0.1,
               "coord_bits": 23.0,
               "ntets": sum(cfg["guarantees"]["ntets"]["band"]) / 2,
               "len_ok_share":
                   sum(cfg["guarantees"]["len_ok_share"]["band"]) / 2}
    rows = checker.judge(numbers, cfg["guarantees"])
    assert {r["name"] for r in rows} == set(cfg["guarantees"])
    assert all(r["ok"] for r in rows)
    # the reading that fails at the parent: dead rows marked live
    rows = checker.judge(dict(numbers, broken=1), cfg["guarantees"])
    assert [r["name"] for r in rows if not r["ok"]] == ["broken"]
