"""The readers PR 28 added, each on a hand-made ``run`` (readers.py says
what one holds): a value where its span or counter is there, None where
it is not, as in a cell on another path or on a program that lacks it."""
import pytest

from byname import load

CELL_READERS = ["split_s", "merge_s", "displace_s", "polish_waves",
                "polish_wave_s", "polish_ops", "api_s", "run_self_s"]


def job(spans, counters):
    """A job as job.run_job returns it: (name, start, end) spans, their
    seconds summed by name, the counters' increase."""
    phases = {}
    for name, s, e in spans:
        phases[name] = phases.get(name, 0.0) + (e - s)
    return {"spans": spans, "phases": phases, "counters": counters}


def grouped_job(shift=0.0):
    t = 100.0 + shift
    spans = [
        ("analysis", t + 0.0, t + 0.3), ("metric", t + 0.3, t + 0.5),
        ("backup", t + 0.5, t + 0.52),
        ("grp split", t + 0.6, t + 0.9), ("grp upload", t + 0.9, t + 1.0),
        ("grp block", t + 1.0, t + 1.3), ("grp block", t + 1.3, t + 1.6),
        ("grp pull", t + 1.6, t + 1.65), ("grp merge", t + 1.65, t + 1.9),
        ("grp displace", t + 1.9, t + 2.0),
        ("grp split", t + 2.0, t + 2.2), ("grp upload", t + 2.2, t + 2.25),
        ("grp block", t + 2.25, t + 2.5),
        ("grp pull", t + 2.5, t + 2.55), ("grp merge", t + 2.55, t + 2.8),
        # a duration folded in by Timers.add: stamped when folded in
        ("adaptation/grp compute", t + 1.95, t + 2.8),
        ("adaptation", t + 0.6, t + 2.8),
        ("polish wave", t + 2.8, t + 3.8), ("polish wave", t + 3.8, t + 4.6),
        ("polish wave", t + 4.6, t + 5.2),
        ("bad-element polish", t + 2.8, t + 5.2),
        ("sequential repair", t + 5.2, t + 5.3),
        ("fem round", t + 5.3, t + 5.4), ("fem conformity", t + 5.3, t + 5.4),
        ("run", t + 0.0, t + 5.5),
    ]
    return job(spans, {"tail.polish_waves": 3.0, "tail.polish_ops": 41.0,
                       "api.set_s": 0.02, "api.get_s": 0.03,
                       "compile.backend_s": 0.25,
                       "compile.trace_lower_s": 0.05,
                       "groups.dispatches": 3.0})


def run_of(jobs, trace=None):
    return {"setup_s": 100.0, "jobs": jobs, "chips": 1, "trace": trace,
            "peaks": None, "window_compiles": 0}


@pytest.mark.parametrize("name,expect", [
    ("split_s", 0.3 + 0.1 + 0.2 + 0.05),
    ("merge_s", 0.05 + 0.25 + 0.05 + 0.25),
    ("displace_s", 0.1),
    ("polish_waves", 3.0),
    ("polish_wave_s", 2.4 / 3),
    ("polish_ops", 41.0),
    ("api_s", 0.05),
    # 5.5 s less [0, 0.52] + [0.6, 5.4]
    ("run_self_s", 5.5 - 0.52 - 4.8),
])
def test_a_reader_finds_its_span_or_counter(name, expect):
    run = run_of([grouped_job(), grouped_job(shift=7.0)])
    assert load("layer_metrics", name).read(run) == pytest.approx(expect)


@pytest.mark.parametrize("name", CELL_READERS)
def test_a_reader_returns_none_where_its_source_is_absent(name):
    """A job of the program as it stood before PR 28, or on a path
    without groups: its Timers phases and counters, nothing else."""
    t = 50.0
    old = job([("analysis", t, t + 0.3), ("metric", t + 0.3, t + 0.5),
               ("compute", t + 1.0, t + 1.3),
               ("adaptation/grp compute", t + 1.0, t + 1.3),
               ("adaptation", t + 0.6, t + 2.8),
               ("bad-element polish", t + 2.8, t + 5.2)],
              {"groups.dispatches": 3.0, "groups.pipeline.compute_s": 0.3})
    assert load("layer_metrics", name).read(run_of([old])) is None


def test_a_job_without_the_span_is_left_out_of_the_mean():
    old = job([("adaptation", 1.0, 2.0)], {})
    run = run_of([old, grouped_job()])
    assert load("layer_metrics", "displace_s").read(run) \
        == pytest.approx(0.1)
    assert load("layer_metrics", "polish_waves").read(run) == 3.0


@pytest.mark.parametrize("name,expect", [
    ("setup_compile_s", 30.0 - 2 * 0.25),
    ("setup_trace_load_s", (12.0 - 2 * 0.05) + 4.0),
])
def test_setup_readers_take_the_window_off_the_process_total(
        monkeypatch, name, expect):
    reader = load("layer_metrics", name)
    totals = {"compile.backend_s": 30.0, "compile.trace_lower_s": 12.0,
              "compile.cache_load_s": 4.0, "groups.dispatches": 9.0}
    monkeypatch.setattr(reader, "program_counters", lambda: dict(totals))
    run = run_of([grouped_job(), grouped_job(shift=7.0)])
    assert reader.read(run) == pytest.approx(expect)
    # a program that lacks the counters
    monkeypatch.setattr(reader, "program_counters",
                        lambda: {"groups.dispatches": 9.0})
    assert reader.read(run) is None


def trace_with(idle_gaps):
    return {"busy_s": 7.0, "window_s": 17.0,
            "breakdown": {"device_ops": [], "idle_gaps": idle_gaps}}


def test_idle_named_share_on_the_two_label_shapes():
    reader = load("layer_metrics", "idle_named_share")
    # "<phase>: <label>" and a label alone; the sub-millisecond gaps are
    # no stall and not counted
    run = run_of([grouped_job()], trace_with([
        ["bad-element polish: polish wave", 6.0],
        ["adaptation: grp merge", 1.0],
        ["adaptation: PjitFunction(convert_element_type)", 0.5],
        ["grp split", 0.5],
        ["metric: bench.run", 1.0],
        ["bench.stage", 0.5],
        ["bad-element polish: nothing named", 0.25],
        ["nothing named", 0.25],
        ["gaps under 1 ms", 3.0],
    ]))
    assert reader.read(run) == pytest.approx(100.0 * 8.0 / 10.0)
    # the program before PR 28: every gap under the benchmark's own mark
    old = run_of([grouped_job()], trace_with([
        ["bad-element polish: bench.run", 8.2], ["metric: bench.run", 0.8],
        ["gaps under 1 ms", 0.01]]))
    assert reader.read(old) == 0.0
    # no trace (an untraced run, a CPU rehearsal), or a device never idle
    assert reader.read(run_of([grouped_job()])) is None
    assert reader.read(run_of([grouped_job()], trace_with(
        [["gaps under 1 ms", 0.004]]))) is None
