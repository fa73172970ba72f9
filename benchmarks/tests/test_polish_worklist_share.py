"""``polish_worklist_share`` (PR 35), as test_polish_skips.py does for PR
33's reader: a value where the tail's two row counters are there, None
on a program that lacks them."""
from byname import load
from test_layer_readers import grouped_job, run_of


def with_rows(candidates, listed, shift=0.0):
    j = grouped_job(shift)
    j["counters"].update({"tail.candidate_rows": candidates,
                          "tail.worklist_rows": listed})
    return j


def test_polish_worklist_share_is_listed_over_candidate_rows_a_job():
    reader = load("layer_metrics", "polish_worklist_share")
    assert reader.read(run_of([with_rows(287781.0, 115336.0)])) == \
        100.0 * 115336.0 / 287781.0
    run = run_of([with_rows(1000.0, 400.0),
                  with_rows(2000.0, 1000.0, shift=7.0)])
    assert reader.read(run) == 45.0
    # a job whose waves changed nothing after the first: a value, not None
    assert reader.read(run_of([with_rows(800.0, 0.0)])) == 0.0


def test_polish_worklist_share_is_none_where_the_counters_are_absent():
    reader = load("layer_metrics", "polish_worklist_share")
    # the program before PR 35: the tail's other counters and no such two
    assert reader.read(run_of([grouped_job()])) is None
    assert reader.read(run_of([])) is None
    # one counter alone is no reading, and no candidate is no share
    half = grouped_job()
    half["counters"]["tail.candidate_rows"] = 800.0
    assert reader.read(run_of([half])) is None
    assert reader.read(run_of([with_rows(0.0, 0.0)])) is None
