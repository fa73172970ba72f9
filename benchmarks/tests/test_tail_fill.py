"""``tail_fill`` (PR 29) on hand-made runs, as test_layer_readers.py
does for PR 28's readers: a value where the tail's two counters are
there, None on a program that lacks them."""
import pytest

from byname import load
from test_layer_readers import grouped_job, job, run_of


def with_tail_rows(live, cap, shift=0.0):
    j = grouped_job(shift)
    j["counters"].update({"tail.rows_live": live, "tail.rows_cap": cap})
    return j


def test_tail_fill_is_the_live_share_of_the_capacity():
    reader = load("layer_metrics", "tail_fill")
    run = run_of([with_tail_rows(32592.0, 48888.0),
                  with_tail_rows(11928.0, 35784.0, shift=7.0)])
    assert reader.read(run) == pytest.approx(
        (100.0 * 32592 / 48888 + 100.0 * 11928 / 35784) / 2)


def test_tail_fill_is_none_where_the_counters_are_absent():
    reader = load("layer_metrics", "tail_fill")
    # the program before PR 29: PR 28's counters and nothing else
    assert reader.read(run_of([grouped_job()])) is None
    # a job that ran no merged tail after one that did: the registry
    # holds the counters, their increase is zero
    quiet = job([("adaptation", 1.0, 2.0)],
                {"tail.rows_live": 0.0, "tail.rows_cap": 0.0})
    assert reader.read(run_of([quiet])) is None
    # and such a job is left out of the mean
    run = run_of([quiet, with_tail_rows(100.0, 150.0)])
    assert reader.read(run) == pytest.approx(100.0 * 100 / 150)
