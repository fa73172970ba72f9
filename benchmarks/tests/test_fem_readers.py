"""``fem_s`` and ``fem_incr_share`` (PR 44), as test_polish_incr_share.py
does for PR 38's reader: a value where the span and the two counters are
there, None on a program that lacks them and on a job that derived no
table."""
import json
import os

from byname import HERE, load
from test_layer_readers import grouped_job, job, run_of


def with_tables(tables, merged, shift=0.0):
    j = grouped_job(shift)
    j["counters"].update({"tail.fem_tables": tables,
                          "tail.fem_tables_merged": merged})
    return j


def test_fem_s_is_the_fem_conformity_phase_a_job():
    reader = load("layer_metrics", "fem_s")
    assert abs(reader.read(run_of([grouped_job()])) - 0.1) < 1e-9
    assert abs(reader.read(run_of([grouped_job(), grouped_job(7.0)]))
               - 0.1) < 1e-9
    # a job that ran no fem round (-nofem) has no such phase
    assert reader.read(run_of([job([("run", 0.0, 1.0)], {})])) is None
    assert reader.read(run_of([])) is None


def test_fem_incr_share_is_merged_over_derived_tables_a_job():
    reader = load("layer_metrics", "fem_incr_share")
    assert reader.read(run_of([with_tables(8.0, 8.0)])) == 100.0
    # a job that regrew once: the round after sorted both in full
    run = run_of([with_tables(12.0, 10.0), with_tables(8.0, 8.0, shift=7.0)])
    assert reader.read(run) == (100.0 * 10.0 / 12.0 + 100.0) / 2
    assert reader.read(run_of([with_tables(4.0, 0.0)])) == 0.0


def test_fem_incr_share_is_none_without_the_counters_or_without_tables():
    reader = load("layer_metrics", "fem_incr_share")
    # the parent's program: the tail's other counters and no such two
    assert reader.read(run_of([grouped_job()])) is None
    assert reader.read(run_of([])) is None
    half = grouped_job()
    half["counters"]["tail.fem_tables"] = 8.0
    assert reader.read(run_of([half])) is None
    # the counters are there and say zero: no round ran with a state
    assert reader.read(run_of([with_tables(0.0, 0.0)])) is None


def test_both_are_declared_for_every_cell():
    with open(os.path.join(HERE, os.pardir, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    for name, unit, better, source in (
            ("fem_s", "s", "lower", "program_span"),
            ("fem_incr_share", "%", "higher", "program_counter")):
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert (m["layer"], m["moves"]) == ("tail", "job_s")
        assert m["workloads"] == cells
