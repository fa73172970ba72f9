"""The six readers of ``iso-scale6`` (PR 43) on recorded spans, as
test_block_readers.py does for PR 37's: a value where the program's ring
holds the fields and a job's counters the counter, None on a program
that lacks them (the parent runs the cell under these files).  The spans
are made with the program's own primitive, closed in the order
``grouped_adapt`` closes them; the numbers are the cell's own first CPU
seed."""
import pytest

from byname import load
from test_block_readers import ring  # noqa: F401  (the fixture)
from test_layer_readers import grouped_job, run_of

FIRST = dict(capP=12775, capT=43118, largest=13825, verts=15625,
             seam_verts=2367, junction_verts=134, pieces=9,
             headroom=100.0 * (1.0 - 1.25 * 13825 / 43118))
SECOND = dict(capP=12775, capT=43118, largest=31292, verts=18010,
              seam_verts=3105, junction_verts=171, pieces=6,
              headroom=100.0 * (1.0 - 1.25 * 31292 / 43118))
MOVED = dict(moved=30011, largest=31292, mean=112350 / 6)
NAMES = ["block_row_ms", "seam_share", "junction_verts", "cap_headroom",
         "displaced_share", "group_imbalance"]


def record_job(otrace, fields=True, passes=2):
    with otrace.span("run"):
        for k, split in enumerate((FIRST, SECOND)[:passes]):
            with otrace.span("grp split", groups=6) as sp:
                if fields:
                    sp.set(**split)
            with otrace.span("grp block", block=0, active=6):
                pass
            if k + 1 < passes:
                with otrace.span("grp displace", layers=2) as sp:
                    if fields:
                        sp.set(**MOVED)


def with_rows(compute_s, rows, dispatches=24.0, shift=0.0):
    j = grouped_job(shift)
    j["counters"].update({"groups.pipeline.compute_s": compute_s,
                          "groups.dispatches": dispatches})
    if rows is not None:
        j["counters"]["groups.rows"] = rows
    return j


@pytest.mark.parametrize("name,expect", [
    ("seam_share", 100.0 * 2367 / 15625),           # the FIRST split's
    ("junction_verts", 134.0),
    ("cap_headroom", 100.0 * (1.0 - 1.25 * 31292 / 43118)),   # the LAST
    ("displaced_share", 100.0 * 30011 / 112350),
    ("group_imbalance", 100.0 * (31292 / (112350 / 6) - 1.0)),
])
def test_a_reader_reads_its_span_fields(ring, name, expect):  # noqa: F811
    record_job(ring)
    got = load("layer_metrics", name).read(run_of([grouped_job()]))
    assert got == pytest.approx(expect, rel=1e-12)
    assert isinstance(got, float)


def test_cap_headroom_stood_ten_per_cent_from_the_edge(ring):  # noqa: F811
    """The cell's own reading: 1.25 x 31,292 of 43,118."""
    record_job(ring)
    got = load("layer_metrics", "cap_headroom").read(run_of([grouped_job()]))
    assert 9.0 < got < 9.5


def test_block_row_ms_is_block_seconds_over_rows_a_job():
    reader = load("layer_metrics", "block_row_ms")
    # 24 blocks of 6 rows in 15.9 s: 110 ms a row
    assert reader.read(run_of([with_rows(15.84, 144.0)])) == \
        pytest.approx(110.0)
    run = run_of([with_rows(15.84, 144.0), with_rows(7.2, 72.0, shift=9.0)])
    assert reader.read(run) == pytest.approx((110.0 + 100.0) / 2)


def test_block_row_ms_is_none_without_the_counter():
    reader = load("layer_metrics", "block_row_ms")
    assert reader.read(run_of([with_rows(15.84, None)])) is None
    assert reader.read(run_of([with_rows(0.0, 0.0, dispatches=0.0)])) is None
    assert reader.read(run_of([])) is None


@pytest.mark.parametrize("name", NAMES[1:])
def test_a_span_reader_is_none_where_the_fields_are_absent(
        ring, name):  # noqa: F811
    reader = load("layer_metrics", name)
    run = run_of([grouped_job()])
    assert reader.read(run) is None             # no span at all
    record_job(ring, fields=False)              # the parent's spans
    assert reader.read(run) is None


@pytest.mark.parametrize("name", ["displaced_share", "group_imbalance"])
def test_a_job_of_one_pass_displaced_nothing(ring, name):  # noqa: F811
    record_job(ring, passes=1)
    assert load("layer_metrics", name).read(run_of([grouped_job()])) is None


def test_zero_junctions_is_a_count(ring):  # noqa: F811
    with ring.span("grp split", groups=2) as sp:
        sp.set(**dict(FIRST, junction_verts=0))
    reader = load("layer_metrics", "junction_verts")
    assert reader.read(run_of([grouped_job()])) == 0.0
