"""``block_programs`` (PR 31), as test_tail_fill.py does for PR 29's
reader: a value where the process's counters hold
``compile.block_programs``, None on a program that lacks the counter."""
from byname import load
from test_layer_readers import grouped_job, run_of


def test_block_programs_is_the_process_total(monkeypatch):
    reader = load("layer_metrics", "block_programs")
    monkeypatch.setattr(reader, "program_counters", lambda: {
        "compile.backend_n": 460.0, "compile.block_programs": 1.0})
    # a process total: what the window's jobs added is not taken off
    warm = grouped_job()
    warm["counters"]["compile.block_programs"] = 0.0
    assert reader.read(run_of([warm])) == 1.0
    monkeypatch.setattr(reader, "program_counters", lambda: {
        "compile.block_programs": 2.0})
    assert reader.read(run_of([grouped_job()])) == 2.0


def test_block_programs_is_none_where_the_counter_is_absent(monkeypatch):
    reader = load("layer_metrics", "block_programs")
    # the program before PR 31: the other compile counters and no such one
    monkeypatch.setattr(reader, "program_counters", lambda: {
        "compile.backend_n": 461.0, "compile.cache_hits": 4.0})
    assert reader.read(run_of([grouped_job()])) is None
    monkeypatch.setattr(reader, "program_counters", dict)
    assert reader.read(run_of([])) is None

