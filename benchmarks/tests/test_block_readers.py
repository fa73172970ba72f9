"""``block_fill``, ``quiet_share``, ``input_tets`` (PR 37), as
test_polish_worklist_share.py does for PR 35's reader: a value where the
program's ring holds the span and a job's counters the counter, None on
a program that lacks them.  The spans are made with the program's own
primitive (``obs.trace.span``), as ``grouped_adapt_pass`` and
``driver.parmmg_run`` make theirs."""
import pytest

from byname import load
from test_layer_readers import grouped_job, run_of


@pytest.fixture
def ring():
    """An empty ring, as job.run_job leaves it when a job starts."""
    from parmmg_tpu.obs import trace as otrace
    otrace.TRACER.reset()
    yield otrace
    otrace.TRACER.reset()


def record_job(otrace, groups=3, largest=(12270, 17989), capT=43118,
               ne_in=36807, split_fields=True, run_fields=True):
    """The spans of one grouped job of two passes, closed in the order
    the program closes them."""
    with otrace.span("run") as run:
        for big in largest:
            with otrace.span("grp split", groups=groups) as sp:
                if split_fields:
                    sp.set(capP=12775, capT=capT, largest=big)
            with otrace.span("grp block", block=0, active=groups):
                pass
        if run_fields:
            run.set(ne_in=ne_in, ne_out=32043, status=0)


def with_quiet(dispatches, skipped, shift=0.0):
    j = grouped_job(shift)
    j["counters"].update({"groups.dispatches": dispatches,
                          "groups.cond_skipped": skipped})
    return j


def test_block_fill_is_the_first_splits_largest_group_over_capT(ring):
    reader = load("layer_metrics", "block_fill")
    record_job(ring)
    assert reader.read(run_of([grouped_job()])) == 100.0 * 12270 / 43118
    # the last job's: job.run_job empties the ring when a job starts
    ring.TRACER.reset()
    record_job(ring, groups=2, largest=(12288, 23158))
    assert reader.read(run_of([grouped_job()])) == 100.0 * 12288 / 43118


def test_input_tets_is_ne_in_of_the_run_span(ring):
    reader = load("layer_metrics", "input_tets")
    record_job(ring)
    assert reader.read(run_of([grouped_job()])) == 36807.0
    ring.TRACER.reset()
    record_job(ring, ne_in=0)       # a count of nothing is a count
    assert reader.read(run_of([grouped_job()])) == 0.0


def test_quiet_share_is_skipped_rows_over_dispatched_rows_a_job(ring):
    reader = load("layer_metrics", "quiet_share")
    record_job(ring)
    # 24 dispatches of 3 rows, none skipped: a value, not None
    assert reader.read(run_of([with_quiet(24.0, 0.0)])) == 0.0
    run = run_of([with_quiet(24.0, 18.0), with_quiet(20.0, 30.0, shift=7.0)])
    assert reader.read(run) == (100.0 * 18 / 72 + 100.0 * 30 / 60) / 2


@pytest.mark.parametrize("name", ["block_fill", "quiet_share", "input_tets"])
def test_a_reader_returns_none_where_the_span_or_counter_is_absent(
        ring, name):
    reader = load("layer_metrics", name)
    run = run_of([with_quiet(24.0, 0.0)])
    # a ring with no span at all: a job on another path, or no job yet
    assert reader.read(run) is None
    assert reader.read(run_of([])) is None
    # spans of those names that carry no such field
    record_job(ring, split_fields=False, run_fields=False)
    if name != "quiet_share":       # `groups` is set when the span opens
        assert reader.read(run) is None


def test_quiet_share_is_none_without_its_counters(ring):
    reader = load("layer_metrics", "quiet_share")
    record_job(ring)
    # the spans and no counter; then a job that dispatched nothing
    assert reader.read(run_of([grouped_job()])) is None
    assert reader.read(run_of([with_quiet(0.0, 0.0)])) is None
    assert reader.read(run_of([])) is None
