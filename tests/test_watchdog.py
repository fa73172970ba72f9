"""Hang-proofing tier-1 (host-only): deadline watchdogs, heartbeat
leases, the crash-loop breaker, and the ``hang=S`` fault action.

Everything here is stdlib-speed — no XLA programs, no subprocesses.
The end-to-end hang drills (a wedged grouped chunk retried to parity,
a wedged pod
worker killed by the heartbeat lease and resumed bit-identically) live
in ``run_tests.sh --chaos`` / ``--multihost``; tier-1 pins the
mechanism contracts those drills compose.
"""
import importlib.util
import os
import time

import pytest

from parmmg_tpu.resilience import checkpoint as ckpt
from parmmg_tpu.resilience import faults
from parmmg_tpu.resilience import watchdog as wd
from parmmg_tpu.resilience.watchdog import (Deadline, WatchdogTimeout,
                                            beat, deadline_knob,
                                            run_with_deadline,
                                            stale_ranks)


def _counter(name):
    from parmmg_tpu.obs.metrics import REGISTRY
    return REGISTRY.counter(name).value


def _site(prefix):
    """Unique watchdog site per call: first-use grace state
    (``_FIRST_DONE``) is process-global, so tests must never share a
    site name across runs in one process."""
    return f"{prefix}.{os.urandom(4).hex()}"


# ---------------------------------------------------------------------------
# polled deadlines
# ---------------------------------------------------------------------------
def test_deadline_polled_expiry():
    before = _counter("resilience.watchdog_timeouts")
    with Deadline(0.05, site="t.polled") as dl:
        assert not dl.expired
        dl.check()                      # armed but not expired: no-op
        assert dl.remaining() is not None
        time.sleep(0.08)
        assert dl.expired
        with pytest.raises(WatchdogTimeout) as ei:
            dl.check()
        assert ei.value.site == "t.polled"
        assert ei.value.seconds == pytest.approx(0.05)
    assert isinstance(ei.value, RuntimeError)   # the ladder-shape pin
    assert _counter("resilience.watchdog_timeouts") == before + 1


def test_deadline_disarmed_level_never_expires():
    with Deadline(0, site="t.off") as dl:
        assert dl.remaining() is None
        time.sleep(0.02)
        assert not dl.expired
        dl.check()                      # disarmed: never raises


def test_deadline_nested_outer_budget_wins():
    """A tight inner deadline can never mask an exhausted outer one:
    check() reports the earliest-armed expired level."""
    with Deadline(0.05, site="t.outer"):
        with Deadline(60, site="t.inner") as inner:
            time.sleep(0.08)
            with pytest.raises(WatchdogTimeout) as ei:
                inner.check()
            assert ei.value.site == "t.outer"
    # both levels popped: a fresh check is clean
    Deadline(0, site="t.clean").check()


def test_deadline_knob_parsing(monkeypatch):
    monkeypatch.delenv("PARMMG_DEADLINE_DISPATCH_S", raising=False)
    assert deadline_knob("PARMMG_DEADLINE_DISPATCH_S") == 0.0
    monkeypatch.setenv("PARMMG_DEADLINE_DISPATCH_S", "2.5")
    assert deadline_knob("PARMMG_DEADLINE_DISPATCH_S") == 2.5
    monkeypatch.setenv("PARMMG_DEADLINE_DISPATCH_S", "junk")
    assert deadline_knob("PARMMG_DEADLINE_DISPATCH_S") == 0.0
    monkeypatch.setenv("PARMMG_DEADLINE_DISPATCH_S", "-3")
    assert deadline_knob("PARMMG_DEADLINE_DISPATCH_S") == 0.0


# ---------------------------------------------------------------------------
# monitor-thread deadlines
# ---------------------------------------------------------------------------
def test_run_with_deadline_inline_when_off():
    assert run_with_deadline(lambda: 41 + 1, 0, _site("t.inline")) == 42


def test_run_with_deadline_value_and_exception_passthrough(monkeypatch):
    monkeypatch.setenv("PARMMG_DEADLINE_GRACE_S", "0")
    assert run_with_deadline(lambda: {"v": 7}, 5,
                             _site("t.value")) == {"v": 7}

    def boom():
        raise KeyError("relayed")

    with pytest.raises(KeyError, match="relayed"):
        run_with_deadline(boom, 5, _site("t.exc"))


def test_run_with_deadline_timeout(monkeypatch):
    monkeypatch.setenv("PARMMG_DEADLINE_GRACE_S", "0")
    before = _counter("resilience.watchdog_timeouts")
    site = _site("t.hang")
    with pytest.raises(WatchdogTimeout) as ei:
        run_with_deadline(lambda: time.sleep(0.5), 0.05, site)
    assert ei.value.site == site
    # the abandoned worker rides on the exception for callers that
    # serialize on shared state (the serve daemon waits it out)
    assert ei.value.thread is not None and ei.value.thread.daemon
    assert _counter("resilience.watchdog_timeouts") == before + 1
    ei.value.thread.join(timeout=2)


def test_first_use_grace_covers_cold_call_only(monkeypatch):
    """The first guarded call at a site gets the compile grace on top
    of its deadline; completing it consumes the grace, so the second
    identically-slow call times out."""
    monkeypatch.setenv("PARMMG_DEADLINE_GRACE_S", "0.4")
    site = _site("t.grace")
    assert wd.first_use_grace(site) == pytest.approx(0.4)
    slow = lambda: (time.sleep(0.15), "done")[1]  # noqa: E731
    assert run_with_deadline(slow, 0.05, site) == "done"
    assert wd.first_use_grace(site) == 0.0
    with pytest.raises(WatchdogTimeout):
        run_with_deadline(slow, 0.05, site)


# ---------------------------------------------------------------------------
# heartbeat leases
# ---------------------------------------------------------------------------
def test_beat_noop_without_supervisor_dir(monkeypatch):
    monkeypatch.delenv("PARMMG_MH_HEARTBEAT_DIR", raising=False)
    assert beat() is None


def test_beat_and_stale_ranks(tmp_path, monkeypatch):
    d = str(tmp_path / "hb")
    monkeypatch.setenv("PARMMG_MH_HEARTBEAT_DIR", d)
    monkeypatch.setenv("PARMMG_HEARTBEAT_S", "0.05")
    monkeypatch.setattr(wd, "_HB", {"last": 0.0})
    before = _counter("resilience.heartbeats")
    p = beat(rank=3)
    assert p is not None and p.endswith("hb.3") and os.path.exists(p)
    assert _counter("resilience.heartbeats") == before + 1
    assert beat(rank=3) is None         # throttled inside the interval

    now = time.time()
    # fresh lease: not stale
    assert stale_ranks(d, 5.0, [3], now=now) == []
    # rank 1 never beat: a missing heartbeat file is NEVER stale
    # (startup + cold compile are covered by the phase timeout)
    assert stale_ranks(d, 5.0, [1, 3], now=now) == []
    # backdate rank 3 past the lease: revoked
    os.utime(p, (now - 10, now - 10))
    assert stale_ranks(d, 5.0, [1, 3], now=now) == [3]
    # lease <= 0 disables the whole mechanism
    assert stale_ranks(d, 0.0, [3], now=now) == []


# ---------------------------------------------------------------------------
# crash-loop breaker
# ---------------------------------------------------------------------------
def test_crash_loop_breaker_threshold(tmp_path, monkeypatch):
    monkeypatch.setenv("PARMMG_CKPT_DIR", str(tmp_path))
    monkeypatch.setenv("PARMMG_RESUME_MAX", "2")
    before = _counter("resilience.crash_loops")
    assert ckpt.crash_loop("t15", "fp", 1) == (1, False)
    assert ckpt.crash_loop("t15", "fp", 1) == (2, False)
    n, esc = ckpt.crash_loop("t15", "fp", 1)
    assert (n, esc) == (3, True)        # the attempt AFTER resume_max
    assert _counter("resilience.crash_loops") == before + 1
    # counts are per-(fingerprint, pass): the next pass starts fresh
    assert ckpt.crash_loop("t15", "fp", 2) == (1, False)
    assert ckpt.crash_loop("t15", "other", 1) == (1, False)
    # write=False computes the decision without persisting the bump
    # (non-zero pod ranks): the stored count stays at 3
    assert ckpt.crash_loop("t15", "fp", 1, write=False) == (4, True)
    assert ckpt.crash_loop("t15", "fp", 1, write=False) == (4, True)


def test_crash_loop_without_ckpt_dir_never_escalates(monkeypatch):
    monkeypatch.delenv("PARMMG_CKPT_DIR", raising=False)
    for _ in range(3):
        assert ckpt.crash_loop("t15", "fp", 1) == (1, False)


# ---------------------------------------------------------------------------
# hang=S fault action
# ---------------------------------------------------------------------------
@pytest.fixture
def arm(monkeypatch):
    def _arm(spec):
        monkeypatch.setenv("PARMMG_FAULT", spec)
        faults.FAULTS.reset()
    yield _arm
    faults.FAULTS.reset()


def test_hang_grammar():
    rules = faults.parse_fault_spec("halo.exchange:hang=2.5;nth-2")
    r = rules["halo.exchange"]
    assert r.hang == 2.5 and r.nth == 2
    with pytest.raises(ValueError, match="hang must be > 0"):
        faults.parse_fault_spec("dispatch.chunk:hang=0")
    with pytest.raises(ValueError, match="unparseable fault trigger"):
        faults.parse_fault_spec("dispatch.chunk:frob=1")


def test_faultpoint_hang_sleeps_and_returns(arm):
    arm("dispatch.chunk:hang=0.1")
    before = _counter("resilience.faults_injected")
    t0 = time.monotonic()
    faults.faultpoint("dispatch.chunk")     # the wedge: NO raise
    assert time.monotonic() - t0 >= 0.09
    assert _counter("resilience.faults_injected") == before + 1


def test_fault_trigger_hang_never_flips_condition(arm):
    arm("analysis.ks_overflow:hang=0.05")
    t0 = time.monotonic()
    assert faults.fault_trigger("analysis.ks_overflow") is False
    assert time.monotonic() - t0 >= 0.04


def test_fired_rule_carries_the_hang_action(arm):
    arm("halo.exchange:hang=1")
    assert faults.FAULTS.fired_rule("halo.exchange").hang == 1.0
    arm("halo.exchange")
    assert faults.FAULTS.fired_rule("halo.exchange").hang is None
    assert faults.FAULTS.fired_rule("dispatch.chunk") is None


# ---------------------------------------------------------------------------
# soak schedule determinism (stdlib import — no campaign execution)
# ---------------------------------------------------------------------------
def _load_soak():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "chaos_soak.py")
    spec = importlib.util.spec_from_file_location("chaos_soak_t1", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_soak_schedule_is_pure_function_of_seed():
    soak = _load_soak()
    a = soak.build_schedule(11, 3)
    assert a == soak.build_schedule(11, 3)
    assert a != soak.build_schedule(12, 3)
    assert len(a) == 3 and [s["run"] for s in a] == [0, 1, 2]
    for s in a:
        assert s["site"] in faults.SITES
        assert s["fault"].split(":")[0] in faults.SITES
        assert s["expect"] in ("parity", "lowfailure", "quarantine")
    # the menu spans the FULL registry — no site escapes the soak
    assert set(soak.sites_in_menu()) == set(faults.SITES)


# ---------------------------------------------------------------------------
# serve daemon wedge bit (host-only stub driver)
# ---------------------------------------------------------------------------
class _WedgePool:
    steps = 0
    quarantined = ()

    def active_tenants(self):
        return []


class _WedgedDriver:
    """service_once sleeps past the step deadline every call — the
    wedged-loop shape, no jax."""

    def __init__(self, sleep_s):
        self.pool = _WedgePool()
        self.queue = []
        self.requests = {}
        self.sleep_s = sleep_s

    def service_once(self):
        time.sleep(self.sleep_s)
        return False


def test_daemon_wedge_flips_healthz(monkeypatch):
    from parmmg_tpu.serve.client import ServeClient
    from parmmg_tpu.serve.daemon import PoolDaemon
    monkeypatch.setenv("PARMMG_DEADLINE_SERVE_S", "0.05")
    monkeypatch.setenv("PARMMG_DEADLINE_GRACE_S", "0")
    before = _counter("serve.step_timeouts")
    d = PoolDaemon(driver=_WedgedDriver(0.6), port=0,
                   idle_sleep_s=0.01).start()
    try:
        cl = ServeClient(port=d.port, timeout_s=10)
        h = None
        for _ in range(150):
            h = cl.health()             # lock-free even while wedged
            if h["wedged"]:
                break
            time.sleep(0.02)
        assert h is not None and h["wedged"] is True
        assert h["ok"] is False and h["loop_alive"] is True
        assert _counter("serve.step_timeouts") >= before + 1
    finally:
        d.shutdown()
    assert not d.alive()
