"""``block_perm_gathers`` (PR 45): a value from the static counts of
the block program's scope map (stubbed here, as ``test_block_sorts.py``
stubs it), None in an untraced run or a CPU rehearsal, on a program that
has no such module, lowered no block or cannot have its map cheaply, and
on a program whose map does not count them (the parent's); any other
failure of the map is a failure."""
import sys

import pytest

from byname import load
from test_block_sorts import COUNTS, TRACE, stub  # noqa: F401 (fixture)
from test_layer_readers import grouped_job, run_of

WITH = dict(COUNTS, perm_gathers=6,
            perm_gathers_by_phase={"cyc.table": 4, "cyc.adjacency": 2})


def read(run):
    return load("layer_metrics", "block_perm_gathers").read(run)


def test_a_value_from_the_block_programs_static_counts(stub):
    calls = stub(WITH)
    assert read(run_of([grouped_job()], trace=TRACE)) == 6.0
    # the entry's default program: the one the window's jobs ran
    assert calls == [((), {})]


def test_zero_is_a_value(stub):
    stub(dict(WITH, perm_gathers=0, perm_gathers_by_phase={}))
    assert read(run_of([grouped_job()], trace=TRACE)) == 0.0


def test_none_on_a_program_whose_map_does_not_count_them(stub):
    """The parent's ``map_from_text`` has ``sorts`` and
    ``scalar_gathers`` and no ``perm_gathers``: nothing to read, and
    nothing raised."""
    stub(dict(COUNTS, scalar_gathers=0, scalar_gathers_by_phase={}))
    assert read(run_of([grouped_job()], trace=TRACE)) is None


def test_none_in_an_untraced_run_and_no_map_is_built(stub):
    calls = stub(WITH)
    assert read(run_of([grouped_job()])) is None
    assert calls == []


@pytest.mark.parametrize("error", [
    KeyError("groups.adapt_block: no program lowered in this process"),
    LookupError("groups.adapt_block: no persistent compile cache"),
])
def test_none_where_the_program_has_no_map(stub, error):
    stub(error=error)
    assert read(run_of([grouped_job()], trace=TRACE)) is None


def test_none_on_the_program_before_the_module(monkeypatch):
    monkeypatch.setitem(sys.modules, "parmmg_tpu.obs.devtime", None)
    assert read(run_of([grouped_job()], trace=TRACE)) is None


def test_a_map_that_fails_otherwise_is_not_swallowed(stub):
    stub(error=RuntimeError("the compiler said no"))
    with pytest.raises(RuntimeError):
        read(run_of([grouped_job()], trace=TRACE))
