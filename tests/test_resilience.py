"""Resilience subsystem tests (parmmg_tpu/resilience + satellites).

Tier-1 tests pin the host-side state machines only — fault-spec
parsing, nth/every-k/probability triggers, the retry/backoff/deadline
wrapper, ladder ordering and accounting, checkpoint save/load
atomicity, the serve quarantine bookkeeping — no XLA compiles (the
870s gate is tight; ROADMAP budget note).  The end-to-end injected
runs (worker kill mid-polish, dispatch fault mid-pass, checkpoint/
resume bit-identity) ride the slow tier here and the in-process
``run_tests.sh --chaos`` gate (scripts/chaos_check.py).
"""
import os

import numpy as np
import pytest

from parmmg_tpu.resilience.faults import (FAULTS, FaultRule,
                                          parse_fault_spec)
from parmmg_tpu.resilience.recover import (LADDER, RetryBudgetExhausted,
                                           ladder_step, retry_call)


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("PARMMG_FAULT", raising=False)
    FAULTS.reset()
    yield
    FAULTS.reset()


# ---------------------------------------------------------------------------
# fault-spec grammar + trigger semantics
# ---------------------------------------------------------------------------
def test_fault_spec_grammar():
    r = parse_fault_spec(
        "dispatch.chunk:nth-3,halo.exchange,io.checkpoint:every-2,"
        "serve.slot_step:key=t7;p=0.5;seed=9")
    assert r["dispatch.chunk"].nth == 3
    assert r["halo.exchange"].nth is None \
        and r["halo.exchange"].every is None
    assert r["io.checkpoint"].every == 2
    s = r["serve.slot_step"]
    assert (s.key, s.p, s.seed) == ("t7", 0.5, 9)
    # bare integer == nth
    assert parse_fault_spec("dispatch.chunk:2")["dispatch.chunk"].nth == 2


def test_fault_spec_rejects_garbage():
    with pytest.raises(ValueError, match="unknown fault site"):
        parse_fault_spec("no.such.site")
    with pytest.raises(ValueError, match="unparseable"):
        parse_fault_spec("dispatch.chunk:sometimes")
    with pytest.raises(ValueError, match="nth"):
        parse_fault_spec("dispatch.chunk:nth-0")


def test_trigger_nth_fires_exactly_once():
    r = FaultRule("dispatch.chunk", nth=3)
    assert [r.fires(None) for _ in range(6)] == \
        [False, False, True, False, False, False]


def test_trigger_every_k_is_periodic():
    r = FaultRule("dispatch.chunk", every=2)
    assert [r.fires(None) for _ in range(6)] == \
        [False, True, False, True, False, True]


def test_trigger_probability_seeded_reproducible():
    r1 = FaultRule("dispatch.chunk", p=0.5, seed=4)
    r2 = FaultRule("dispatch.chunk", p=0.5, seed=4)
    assert [r1.fires(None) for _ in range(32)] == \
        [r2.fires(None) for _ in range(32)]
    r_always = FaultRule("dispatch.chunk", p=1.0)
    assert all(r_always.fires(None) for _ in range(4))
    r0 = FaultRule("dispatch.chunk", p=0.0)
    assert not any(r0.fires(None) for _ in range(4))


def test_trigger_key_filter_gates_counting():
    # non-matching hits must not advance the counter: the poison
    # tenant's nth-1 fires on ITS first hit regardless of cohort order
    r = FaultRule("serve.slot_step", nth=1, key="t1")
    assert not r.fires("t0")
    assert r.fires("t1")
    assert not r.fires("t1")


def test_registry_reads_env_and_counts_hits(monkeypatch):
    monkeypatch.setenv("PARMMG_FAULT", "halo.exchange:nth-1")
    FAULTS.reset()
    # hit counters live with the parsed spec: nth-1 fires once
    assert FAULTS.should_fire("halo.exchange")
    assert not FAULTS.should_fire("halo.exchange")
    # changing the knob rebuilds rules with fresh counters
    monkeypatch.setenv("PARMMG_FAULT", "halo.exchange:nth-1;seed=0")
    assert FAULTS.should_fire("halo.exchange")


def test_faultpoint_raises_real_shapes(monkeypatch):
    from parmmg_tpu.resilience.faults import faultpoint
    monkeypatch.setenv("PARMMG_FAULT", "io.checkpoint")
    FAULTS.reset()
    with pytest.raises(OSError, match="injected fault"):
        faultpoint("io.checkpoint")
    monkeypatch.setenv("PARMMG_FAULT", "dispatch.chunk")
    FAULTS.reset()
    with pytest.raises(Exception) as ei:
        faultpoint("dispatch.chunk")
    # XlaRuntimeError subclasses RuntimeError; the message carries the
    # canonical INTERNAL: status prefix either way
    assert isinstance(ei.value, RuntimeError)
    assert "INTERNAL" in str(ei.value)


def test_unarmed_faultpoint_is_free(monkeypatch):
    from parmmg_tpu.resilience.faults import fault_trigger, faultpoint
    faultpoint("dispatch.chunk")          # no env: must not raise
    assert fault_trigger("analysis.ks_overflow") is False


# ---------------------------------------------------------------------------
# retry/backoff/deadline wrapper
# ---------------------------------------------------------------------------
def test_retry_succeeds_after_transient_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    assert retry_call(flaky, "t", max_retries=2, base_s=0) == "ok"
    assert len(calls) == 3


def test_retry_budget_exhaustion_chains_cause():
    def always():
        raise RuntimeError("down")

    with pytest.raises(RetryBudgetExhausted) as ei:
        retry_call(always, "t", max_retries=1, base_s=0)
    assert ei.value.site == "t"
    assert isinstance(ei.value.__cause__, RuntimeError)


def test_retry_never_retries_capacity_signals():
    calls = []

    def oom():
        calls.append(1)
        raise MemoryError("group capacity exhausted")

    with pytest.raises(MemoryError):
        retry_call(oom, "t", max_retries=3, base_s=0)
    assert len(calls) == 1                # deterministic: no re-run


def _xla_error(msg):
    from jax.errors import JaxRuntimeError
    return JaxRuntimeError(msg)


@pytest.mark.parametrize("make", [
    lambda: NotImplementedError("Unimplemented primitive in Pallas TPU "
                                "lowering: cumsum"),
    lambda: ValueError("Cannot store scalars to VMEM"),
    lambda: TypeError("unexpected keyword argument"),
    lambda: _xla_error("INVALID_ARGUMENT: shape mismatch"),
    lambda: _xla_error("RESOURCE_EXHAUSTED: Out of memory"),
    lambda: _xla_error("INTERNAL: Mosaic failed to compile TPU kernel"),
], ids=["notimpl", "value", "type", "xla-invalid", "xla-oom",
        "xla-mosaic"])
def test_retry_passes_deterministic_failures_through(make):
    calls = []

    def bad():
        calls.append(1)
        raise make()

    with pytest.raises(type(make())):
        retry_call(bad, "t", max_retries=3, base_s=0)
    assert len(calls) == 1                # a retry cannot change it
    # ... also as the inline attempt the pipelined dispatch already lost
    with pytest.raises(type(make())):
        retry_call(lambda: "ok", "t", max_retries=3, base_s=0,
                   initial_failure=make())


def test_retry_retries_transport_status():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise _xla_error("UNAVAILABLE: connection reset")
        return "ok"

    assert retry_call(flaky, "t", max_retries=2, base_s=0) == "ok"


def test_retry_initial_failure_consumes_attempt_zero():
    # the pipelined dispatch's inline attempt already failed: with
    # PARMMG_RETRY_MAX=0 that exhausts immediately, fn never re-runs
    calls = []
    with pytest.raises(RetryBudgetExhausted) as ei:
        retry_call(lambda: calls.append(1), "t", max_retries=0,
                   base_s=0, initial_failure=RuntimeError("first"))
    assert calls == []
    assert str(ei.value.__cause__) == "first"
    # with budget, the initial failure counts as attempt 0 and the
    # wrapper proceeds to a (successful) re-attempt
    assert retry_call(lambda: "ok", "t", max_retries=1, base_s=0,
                      initial_failure=RuntimeError("first")) == "ok"


def test_retry_deadline_stops_early():
    calls = []

    def slow_fail():
        calls.append(1)
        raise RuntimeError("down")

    with pytest.raises(RetryBudgetExhausted):
        retry_call(slow_fail, "t", max_retries=50, base_s=0.02,
                   deadline_s=0.01)
    assert len(calls) <= 3                # deadline, not the 50 budget


def test_retry_env_knobs(monkeypatch):
    from parmmg_tpu.resilience.recover import retry_env
    monkeypatch.setenv("PARMMG_RETRY_MAX", "7")
    monkeypatch.setenv("PARMMG_RETRY_BASE_S", "0.5")
    monkeypatch.setenv("PARMMG_RETRY_DEADLINE_S", "9")
    assert retry_env() == (7, 0.5, 9.0)


# ---------------------------------------------------------------------------
# escalation ladder
# ---------------------------------------------------------------------------
def test_ladder_order_is_the_documented_escalation():
    assert LADDER == ("retry", "mh_allgather", "halo_dense",
                      "host_analysis",
                      "merged_polish", "lowfailure")


def test_ladder_step_counts_and_traces():
    from parmmg_tpu.obs.metrics import REGISTRY
    from parmmg_tpu.obs.trace import TRACER
    before = REGISTRY.counter("resilience.host_analysis").value
    n0 = len(TRACER.ring)
    ladder_step("host_analysis", site="analysis.ks_overflow")
    assert REGISTRY.counter("resilience.host_analysis").value == \
        before + 1
    evs = [r for r in list(TRACER.ring)[n0:]
           if r.get("kind") == "event"
           and r.get("name") == "resilience.ladder"]
    assert evs and evs[-1]["step"] == "host_analysis"
    with pytest.raises(ValueError, match="unknown ladder step"):
        ladder_step("panic")


# ---------------------------------------------------------------------------
# pass checkpoints (host round-trip; resume bit-identity is chaos/slow)
# ---------------------------------------------------------------------------
def _tiny_mesh():
    from parmmg_tpu.core.mesh import MESH_FIELDS, Mesh
    rng = np.random.RandomState(0)
    kw = {}
    for f in MESH_FIELDS:
        if f in ("npoin", "nelem"):
            kw[f] = np.asarray(4, np.int32)
        elif f in ("vmask", "tmask"):
            kw[f] = rng.rand(6) < 0.5
        elif f == "vert":
            kw[f] = rng.rand(6, 3)
        elif f == "tet":
            kw[f] = rng.randint(0, 6, (6, 4)).astype(np.int32)
        elif f == "adja":
            kw[f] = np.full((6, 4), -1, np.int32)
        else:
            kw[f] = np.zeros((6,), np.int32) if f.startswith("v") \
                else np.zeros((6, 4), np.int32)
    return Mesh(**kw)


def test_checkpoint_roundtrip_and_latest(tmp_path, monkeypatch):
    from parmmg_tpu.resilience import checkpoint as ck
    monkeypatch.setenv("PARMMG_CKPT_DIR", str(tmp_path))
    monkeypatch.setenv("PARMMG_CKPT_EVERY", "1")
    m = _tiny_mesh()
    met = np.linspace(0, 1, 6)
    part = np.array([0, 1, 2, 0], np.int32)
    for it in (0, 1):
        assert ck.save_pass_checkpoint("t", it, m, met, part)
    # a kill mid-write leaves only .tmp partials: never resumed from
    (tmp_path / "t.pass5.npz.tmp").write_bytes(b"partial")
    path, it = ck.latest_pass_checkpoint("t")
    assert it == 1 and path.endswith("t.pass1.npz")
    m2, met2, part2, it2 = ck.load_pass_checkpoint(path)
    assert it2 == 1
    assert (np.asarray(m2.vert) == np.asarray(m.vert)).all()
    assert (met2 == met).all() and (part2 == part).all()


def test_checkpoint_cadence_and_disabled(tmp_path, monkeypatch):
    from parmmg_tpu.resilience import checkpoint as ck
    monkeypatch.delenv("PARMMG_CKPT_DIR", raising=False)
    assert ck.save_pass_checkpoint("t", 0, None, None, None) is None
    monkeypatch.setenv("PARMMG_CKPT_DIR", str(tmp_path))
    monkeypatch.setenv("PARMMG_CKPT_EVERY", "2")
    assert not ck.ckpt_due(0) and ck.ckpt_due(1) and not ck.ckpt_due(2)


def test_checkpoint_fault_is_absorbed(tmp_path, monkeypatch):
    from parmmg_tpu.obs.metrics import REGISTRY
    from parmmg_tpu.resilience import checkpoint as ck
    monkeypatch.setenv("PARMMG_CKPT_DIR", str(tmp_path))
    monkeypatch.setenv("PARMMG_CKPT_EVERY", "1")
    monkeypatch.setenv("PARMMG_FAULT", "io.checkpoint")
    FAULTS.reset()
    before = REGISTRY.counter("resilience.checkpoint_failures").value
    # the injected OSError must be swallowed: run > checkpoint
    assert ck.save_pass_checkpoint("t", 0, _tiny_mesh(),
                                   np.zeros(6), None) is None
    assert REGISTRY.counter("resilience.checkpoint_failures").value == \
        before + 1
    assert list(tmp_path.iterdir()) == []


def test_checkpoint_fingerprint_guards_stale_resume(tmp_path,
                                                    monkeypatch):
    """A reused ckpt dir must never silently resume a checkpoint from
    a DIFFERENT run: the stored input fingerprint has to match."""
    from parmmg_tpu.resilience import checkpoint as ck
    monkeypatch.setenv("PARMMG_CKPT_DIR", str(tmp_path))
    monkeypatch.setenv("PARMMG_CKPT_EVERY", "1")
    m = _tiny_mesh()
    fp_a = ck.run_fingerprint(m, np.zeros(6), 16, 2)
    fp_b = ck.run_fingerprint(m, np.ones(6), 16, 2)   # different met
    assert fp_a != fp_b
    assert ck.save_pass_checkpoint("t", 0, m, np.zeros(6), None,
                                   fingerprint=fp_a)
    assert ck.latest_pass_checkpoint("t", fingerprint=fp_a) is not None
    assert ck.latest_pass_checkpoint("t", fingerprint=fp_b) is None
    # legacy checkpoints without a stored fingerprint are also refused
    # when the caller asks for identity; accepted when it doesn't
    assert ck.save_pass_checkpoint("u", 0, m, np.zeros(6), None)
    assert ck.latest_pass_checkpoint("u", fingerprint=fp_a) is None
    assert ck.latest_pass_checkpoint("u") is not None


def test_latest_checkpoint_none_without_dir(monkeypatch):
    from parmmg_tpu.resilience import checkpoint as ck
    monkeypatch.delenv("PARMMG_CKPT_DIR", raising=False)
    assert ck.latest_pass_checkpoint("t") is None


# ---------------------------------------------------------------------------
# serve quarantine bookkeeping (pool state machine, no dispatch)
# ---------------------------------------------------------------------------
def test_slot_fault_quarantine_threshold(monkeypatch):
    from parmmg_tpu.obs.metrics import REGISTRY
    from parmmg_tpu.serve.pool import SlotPool
    p = SlotPool(slots_per_bucket=2, max_slot_retries=2)
    p.admit("a", 27, 48)
    s = p.slot_of("a")
    before = REGISTRY.counter("serve.quarantined").value
    assert p._note_slot_fault(s, RuntimeError("boom")) is False
    assert s.faults == 1 and not s.failed
    assert p._note_slot_fault(s, RuntimeError("boom")) is True
    assert "quarantined after 2" in s.failed
    assert p.quarantined == ["a"]
    assert REGISTRY.counter("serve.quarantined").value == before + 1
    # a failed slot is no longer active (the pool loop retires it)
    assert "a" not in p.active_tenants()


def test_serve_max_retries_env(monkeypatch):
    from parmmg_tpu.serve.pool import SlotPool
    monkeypatch.setenv("PARMMG_SERVE_MAX_RETRIES", "5")
    assert SlotPool(slots_per_bucket=1).max_slot_retries == 5
    # constructor arg wins; floor of 1 enforced
    assert SlotPool(slots_per_bucket=1,
                    max_slot_retries=0).max_slot_retries == 1


# ---------------------------------------------------------------------------
# slow tier: end-to-end injected-fault runs (XLA compiles)
# ---------------------------------------------------------------------------
def _grouped_case():
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.utils.fixtures import cube_mesh
    vert, tet = cube_mesh(2)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, 0.35, m.vert.dtype)
    return m, met


def _bytes(mesh, met):
    from parmmg_tpu.core.mesh import MESH_FIELDS
    return tuple(np.asarray(getattr(mesh, f)).tobytes()
                 for f in MESH_FIELDS) + (np.asarray(met).tobytes(),)


@pytest.mark.slow
def test_dispatch_fault_mid_pass_recovers_bitwise(monkeypatch):
    """A transient chunk-dispatch fault mid-pass retries serially and
    the pass result is bit-identical to the fault-free run."""
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "2")
    monkeypatch.setenv("PARMMG_RETRY_BASE_S", "0")
    m, met = _grouped_case()
    ref = grouped_adapt_pass(m, met, 3, cycles=2)
    # fault the SECOND chunk dispatch: mid-pass, not at the boundary
    monkeypatch.setenv("PARMMG_FAULT", "dispatch.chunk:nth-2")
    FAULTS.reset()
    m2, met2 = _grouped_case()
    got = grouped_adapt_pass(m2, met2, 3, cycles=2)
    assert _bytes(ref[0], ref[1]) == _bytes(got[0], got[1])


@pytest.mark.slow
def test_checkpoint_resume_bit_identity(tmp_path, monkeypatch):
    """A run resumed from the pass-0 checkpoint (the killed-run replay)
    finishes bit-identical to the uninterrupted 2-pass run."""
    from parmmg_tpu.parallel.groups import grouped_adapt
    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "2")
    monkeypatch.setenv("PARMMG_CKPT_DIR", str(tmp_path))
    m, met = _grouped_case()
    full = grouped_adapt(m, met, 16, niter=2, cycles=2, ckpt_tag="ck")
    # the kill happened mid-pass-1: its checkpoint never landed
    (tmp_path / "ck.pass1.npz").unlink()
    m2, met2 = _grouped_case()
    resumed = grouped_adapt(m2, met2, 16, niter=2, cycles=2,
                            ckpt_tag="ck", resume=True)
    assert _bytes(*full) == _bytes(*resumed)
