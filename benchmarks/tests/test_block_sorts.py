"""``block_sorts`` (PR 39): a value from the static counts of the block
program's scope map (stubbed here: the map is the program's,
``parmmg_tpu.obs.devtime.scope_map``), None in an untraced run or a CPU
rehearsal (``run["trace"]`` is None) and on a program that has no such
module, lowered no block or cannot have its map cheaply; any other
failure of the map is a failure."""
import sys
import types

import pytest

from byname import load
from test_layer_readers import grouped_job, run_of

TRACE = {"blocks": 24, "block_s": 7.2}      # any reduction of a capture
COUNTS = {"ops": 4000, "scoped": 3880, "sorts": 55,
          "sorts_by_phase": {"cyc.table": 2, "cyc.adjacency": 4}}


@pytest.fixture
def stub(monkeypatch):
    """``parmmg_tpu.obs.devtime`` with a ``scope_map`` that hands back
    the counts it is given and counts its calls."""
    calls = []

    def install(counts=None, error=None):
        mod = types.ModuleType("parmmg_tpu.obs.devtime")

        def scope_map(*a, **k):
            calls.append((a, k))
            if error is not None:
                raise error
            return types.SimpleNamespace(counts=counts)
        mod.scope_map = scope_map
        import parmmg_tpu.obs as obs
        monkeypatch.setitem(sys.modules, "parmmg_tpu.obs.devtime", mod)
        monkeypatch.setattr(obs, "devtime", mod, raising=False)
        return calls
    return install


def read(run):
    return load("layer_metrics", "block_sorts").read(run)


def test_a_value_from_the_block_programs_static_counts(stub):
    calls = stub(COUNTS)
    assert read(run_of([grouped_job()], trace=TRACE)) == 55.0
    # the entry's default program: the one the window's jobs ran
    assert calls == [((), {})]


def test_none_in_an_untraced_run_and_no_map_is_built(stub):
    calls = stub(COUNTS)
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
