"""``polish_skips`` (PR 33), as test_tail_fill.py does for PR 29's
reader: a value where the tail's two skip counters are there (zeros
too), None on a program that lacks them."""
from byname import load
from test_layer_readers import grouped_job, run_of


def with_skips(collapse, adjacency, shift=0.0):
    j = grouped_job(shift)
    j["counters"].update({"tail.collapse_skipped": collapse,
                          "tail.exit_adj_skipped": adjacency})
    return j


def test_polish_skips_is_the_sum_of_the_two_counters_a_job():
    reader = load("layer_metrics", "polish_skips")
    assert reader.read(run_of([with_skips(7.0, 7.0)])) == 14.0
    run = run_of([with_skips(7.0, 7.0), with_skips(6.0, 5.0, shift=7.0)])
    assert reader.read(run) == 12.5
    # a job whose every wave had slivers and 2-3 swaps: a value, not None
    assert reader.read(run_of([with_skips(0.0, 0.0)])) == 0.0


def test_polish_skips_is_none_where_the_counters_are_absent():
    reader = load("layer_metrics", "polish_skips")
    # the program before PR 33: the tail's other counters and no such two
    assert reader.read(run_of([grouped_job()])) is None
    assert reader.read(run_of([])) is None
    # and one counter alone is no reading
    half = grouped_job()
    half["counters"]["tail.collapse_skipped"] = 7.0
    assert reader.read(run_of([half])) is None
