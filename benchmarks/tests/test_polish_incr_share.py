"""``polish_incr_share`` (PR 38), as test_polish_worklist_share.py does
for PR 35's reader: a value where the tail's two table counters are
there, None on a program that lacks them."""
from byname import load
from test_layer_readers import grouped_job, run_of


def with_tables(tables, merged, shift=0.0):
    j = grouped_job(shift)
    j["counters"].update({"tail.tables": tables,
                          "tail.tables_merged": merged})
    return j


def test_polish_incr_share_is_merged_over_derived_tables_a_job():
    reader = load("layer_metrics", "polish_incr_share")
    assert reader.read(run_of([with_tables(26.0, 24.0)])) == \
        100.0 * 24.0 / 26.0
    run = run_of([with_tables(40.0, 30.0),
                  with_tables(20.0, 19.0, shift=7.0)])
    assert reader.read(run) == 85.0
    # a job whose every table was sorted in full: a value, not None
    assert reader.read(run_of([with_tables(5.0, 0.0)])) == 0.0


def test_polish_incr_share_is_none_where_the_counters_are_absent():
    reader = load("layer_metrics", "polish_incr_share")
    # the program before PR 38: the tail's other counters and no such two
    assert reader.read(run_of([grouped_job()])) is None
    assert reader.read(run_of([])) is None
    # one counter alone is no reading, and no table is no share
    half = grouped_job()
    half["counters"]["tail.tables"] = 26.0
    assert reader.read(run_of([half])) is None
    assert reader.read(run_of([with_tables(0.0, 0.0)])) is None
