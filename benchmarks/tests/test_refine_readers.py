"""The six readers of ``iso-refine`` (PR 47) on recorded spans and
counters, as test_scale6_readers.py does for PR 43's: a value where the
program's ring holds the fields and a job's counters the counter, None
on a program that lacks them (the parent runs the accepted cells under
these files, and the new cell not at all).  The spans are made with the
program's own primitive, closed in the order ``grouped_adapt`` closes
them; the numbers are the cell's own first CPU seed."""
import pytest

from byname import load
from test_block_readers import ring  # noqa: F401  (the fixture)
from test_layer_readers import grouped_job, job, run_of

NAMES = ["recuts", "recut_s", "groups_peak", "tile_fill", "split_share",
         "growth"]
BLOCKS = [dict(split=4072, collapse=183, swap=0, moved=1035),
          dict(split=2978, collapse=974, swap=228, moved=1729)]


def record_job(otrace, recut=True, fields=True, ne_in=82944):
    """A job that re-cuts once inside pass 0 and once between the
    passes: 6 -> 18 -> 30 groups."""
    with otrace.span("run") as run:
        if fields:
            run.set(ne_in=ne_in)
        with otrace.span("grp split", groups=6):
            pass
        for b in BLOCKS:
            with otrace.span("grp block", block=0, active=6) as sp:
                if fields:
                    sp.set(tiles=1, rows=6, **b)
        if recut:
            with otrace.span("grp recut", why="overflow", g0=6) as sp:
                with otrace.span("grp split", groups=18):
                    pass
                sp.set(g1=18, ne=239763, largest=14161, headroom=58.9)
            with otrace.span("grp recut", why="pass", g0=18) as sp:
                sp.set(g1=30, ne=318477, largest=16336, headroom=52.6)
            with otrace.span("grp split", groups=30):
                pass


def refine_job(counters, ntets=370973, recut_s=(9.7, 0.9)):
    t = 100.0
    spans = [("grp split", t, t + 4.8), ("grp block", t + 5, t + 6)]
    for k, dur in enumerate(recut_s):
        spans.append(("grp recut", t + 10 + 20 * k, t + 10 + 20 * k + dur))
    spans.append(("run", t, t + 90.0))
    j = job(spans, dict(counters))
    j["numbers"] = {"ntets": ntets}
    return j


COUNTERS = {"groups.recuts": 2.0, "groups.recut_overflow": 1.0,
            "groups.rows": 570.0, "groups.rows_dead": 72.0,
            "groups.dispatches": 95.0}


@pytest.mark.parametrize("name,expect", [
    ("recuts", 2.0),
    ("recut_s", 9.7 + 0.9),
    ("groups_peak", 30.0),
    ("tile_fill", 100.0 * (1.0 - 72.0 / 570.0)),
    ("split_share", 100.0 * (4072 + 2978)
     / (4072 + 183 + 0 + 2978 + 974 + 228)),
    ("growth", 370973 / 82944),
])
def test_a_reader_reads_the_refining_job(ring, name, expect):  # noqa: F811
    record_job(ring)
    got = load("layer_metrics", name).read(run_of([refine_job(COUNTERS)]))
    assert got == pytest.approx(expect, rel=1e-12)
    assert isinstance(got, float)


@pytest.mark.parametrize("name", ["recuts", "recut_s", "groups_peak",
                                  "tile_fill"])
def test_a_job_that_never_recut_has_nothing_to_read(ring, name):  # noqa: F811
    """An accepted cell's job on this program: the counters of the
    scheduler are there (``groups.rows_dead`` at 0), no re-cut is."""
    record_job(ring, recut=False)
    j = grouped_job()
    j["counters"].update({"groups.rows": 144.0, "groups.rows_dead": 0.0,
                          "groups.dispatches": 24.0})
    assert load("layer_metrics", name).read(run_of([j])) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_is_none_on_a_program_without_its_source(
        ring, name):  # noqa: F811
    reader = load("layer_metrics", name)
    run = run_of([grouped_job()])
    assert reader.read(run) is None             # an empty ring
    record_job(ring, recut=False, fields=False)     # spans with no field
    assert reader.read(run) is None
    assert reader.read(run_of([])) is None


def test_split_share_and_growth_read_an_accepted_cells_job(ring):  # noqa: F811
    """The two that list the accepted one-chip cells read the fields the
    parent's spans carry too."""
    record_job(ring, recut=False, ne_in=24576)
    j = grouped_job()
    j["numbers"] = {"ntets": 32861}
    run = run_of([j, dict(j, numbers={"ntets": 33000})])
    assert load("layer_metrics", "growth").read(run) == pytest.approx(
        (32861 + 33000) / 2 / 24576)
    assert 50.0 < load("layer_metrics", "split_share").read(run) < 100.0


def test_tile_fill_counts_only_the_jobs_that_recut():
    reader = load("layer_metrics", "tile_fill")
    plain = refine_job({"groups.rows": 144.0, "groups.rows_dead": 0.0})
    assert reader.read(run_of([plain])) is None
    assert reader.read(run_of([plain, refine_job(COUNTERS)])) == \
        pytest.approx(100.0 * (1.0 - 72.0 / 570.0))
    full = refine_job(dict(COUNTERS, **{"groups.rows_dead": 0.0}))
    assert reader.read(run_of([full])) == 100.0
