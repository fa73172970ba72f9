"""Serve-daemon subsystem tests (serve/admission + autoscale + daemon).

Tier-1 pins the host-side machinery only — the autoscale controller as
a pure function of a metrics snapshot, admission backpressure, the
REAL SlotPool.step streaming worklist (mid-step slot re-rent, dispatch
stubbed), bucket resizing, and the daemon's full HTTP lifecycle over
localhost with a stub pool — no XLA compiles, no jax programs.  The
slow test pins the streaming-admission exactness contract: bit-for-bit
per-tenant parity against the between-steps admission path.  (The
daemon-vs-standalone compile/parity side is gated by
``run_tests.sh --ledger`` serving_gate and ``--serve`` /
scripts/serve_check.py.)
"""
import numpy as np
import pytest

from parmmg_tpu.serve.autoscale import (AutoscaleController, decide,
                                        latency_quantile, read_inputs)
from parmmg_tpu.serve.client import (BackpressureDeferred, ServeClient,
                                     ServeDaemonError)
from parmmg_tpu.serve.daemon import PoolDaemon
from parmmg_tpu.serve.driver import ServeDriver
from parmmg_tpu.serve.pool import SlotPool


# ---------------------------------------------------------------------------
# host-only stubs: real admission/bookkeeping, no XLA
# ---------------------------------------------------------------------------
class HostPool(SlotPool):
    """Real SlotPool admission + slot bookkeeping; load/merge stash the
    payload on the slot instead of splitting/merging (no jax)."""

    def load(self, tenant, mesh, met):
        key, i = self._where[tenant]
        s = self.buckets[key].slots[i]
        s.loaded = True
        s.payload = (mesh, met)

    def merge(self, tenant):
        return self.slot_of(tenant).payload


class InstantPool(HostPool):
    """Serves each loaded tenant after ``steps_to_converge`` advances,
    honoring the streaming ``on_retire`` contract — no dispatches."""

    def __init__(self, steps_to_converge=1, **kw):
        super().__init__(**kw)
        self.steps_to_converge = steps_to_converge

    def step(self, verbose=0, on_retire=None):
        self.steps += 1
        done = []
        while True:
            progressed = False
            for key in sorted(self.buckets):
                for s in self.buckets[key].slots:
                    if s.tenant and s.loaded and not s.converged \
                            and not s.failed \
                            and getattr(s, "stepped", 0) < self.steps:
                        s.stepped = self.steps   # once per step
                        s.c += 1
                        progressed = True
                        if s.c >= self.steps_to_converge:
                            s.converged = True
                            done.append(s.tenant)
                            if on_retire is not None:
                                on_retire([s.tenant])
            if on_retire is None or not progressed:
                break
        return done


class StubDriver(ServeDriver):
    """Host-only driver: quality + RPC staging stubbed (dict meshes)."""

    def _quality(self, mesh, met):
        tet = mesh["tet"] if isinstance(mesh, dict) else mesh.tet
        return {"qmin": 1.0, "qmean": 1.0, "nbad": 0,
                "ntets": int(len(np.asarray(tet)))}

    def stage_payload(self, arrays):
        met = arrays.get("met")
        return {"vert": arrays["vert"], "tet": arrays["tet"]}, met


def _stub_mesh():
    vert = np.arange(12, dtype=np.float64).reshape(4, 3)
    tet = np.array([[0, 1, 2, 3], [1, 2, 3, 0]], np.int32)
    return vert, tet, np.ones(4)


def _daemon(steps=1, **drv_kw):
    pool = InstantPool(steps_to_converge=steps, slots_per_bucket=2)
    drv = StubDriver(pool=pool, autoscale=False, **drv_kw)
    return PoolDaemon(driver=drv, port=0, start_paused=True,
                      idle_sleep_s=0.005).start()


# ---------------------------------------------------------------------------
# autoscale: the pure controller (no jax, no sockets)
# ---------------------------------------------------------------------------
def _inputs(**kw):
    base = {"queue_depth": 0, "occupancy": {}, "slots": {},
            "blocked": {}, "p99_s": 0.0, "slo_violations": 0,
            "deferring": False}
    base.update(kw)
    return base


def test_autoscale_decide_grows_blocked_full_bucket():
    d = decide(_inputs(queue_depth=3, occupancy={"64x192": 2},
                       slots={"64x192": 2}, blocked={"64x192": 3}),
               max_slots=4)
    assert d.grow == {"64x192": 3} and not d.shrink and not d.defer
    # growth ceiling: a bucket at max_slots never grows past it
    d = decide(_inputs(queue_depth=1, occupancy={"64x192": 4},
                       slots={"64x192": 4}, blocked={"64x192": 1}),
               max_slots=4)
    assert d.grow == {}
    # blocked but not full (slots free for other reasons): no grow
    d = decide(_inputs(queue_depth=1, occupancy={"64x192": 1},
                       slots={"64x192": 2}, blocked={"64x192": 1}),
               max_slots=4)
    assert d.grow == {}


def test_autoscale_decide_shrink_is_debounced():
    idle = _inputs(occupancy={"64x192": 0}, slots={"64x192": 3})
    assert decide(idle, idle_evals={"64x192": 2}, shrink_after=3).shrink \
        == {}
    assert decide(idle, idle_evals={"64x192": 3}, shrink_after=3).shrink \
        == {"64x192": 2}
    # never below min_slots; never while work is queued
    floor = _inputs(occupancy={"64x192": 0}, slots={"64x192": 1})
    assert decide(floor, idle_evals={"64x192": 9}).shrink == {}
    busy = _inputs(queue_depth=1, occupancy={"64x192": 0},
                   slots={"64x192": 3})
    assert decide(busy, idle_evals={"64x192": 9}).shrink == {}


def test_autoscale_defer_hysteresis():
    # latch on at the queue bound...
    d = decide(_inputs(queue_depth=4), max_queue=4)
    assert d.defer is True
    # ...stays latched above half the bound...
    d = decide(_inputs(queue_depth=3, deferring=True), max_queue=4)
    assert d.defer is True
    # ...releases at half
    d = decide(_inputs(queue_depth=2, deferring=True), max_queue=4)
    assert d.defer is False
    # p99 SLO breach with work queued also latches
    d = decide(_inputs(queue_depth=1, p99_s=2.0), target_p99_s=1.0)
    assert d.defer is True and "p99" in " ".join(d.reasons)
    # ...and a still-breached p99 holds the latch even with a small
    # queue — the latch must not flap while the condition persists
    d = decide(_inputs(queue_depth=1, p99_s=2.0, deferring=True),
               max_queue=4, target_p99_s=1.0)
    assert d.defer is True
    # p99 breach with an EMPTY queue does not (nothing to shed)
    d = decide(_inputs(queue_depth=0, p99_s=2.0), target_p99_s=1.0)
    assert d.defer is False


def test_autoscale_p99_is_windowed_per_evaluation():
    """The controller must judge RECENT latencies: cold-start compile
    latencies in the lifetime-cumulative histogram must not pin the
    p99 signal (and with it the defer latch) above target forever."""
    ctl = AutoscaleController(max_slots=4, max_queue=0,
                              target_p99_s=1.0)
    hist = {"buckets": {"256.0": 5, "inf": 5}, "count": 5}
    snap = {"gauges": {"serve.queue_depth": 1.0}, "counters": {},
            "histograms": {"serve.latency_s": hist}}
    assert ctl.evaluate(snap).defer is True     # cold window breaches
    ctl.deferring = True                        # (tick would latch it)
    # same cumulative histogram, no NEW observations: recent p99 == 0,
    # nothing hot -> the latch releases as the queue drains
    snap2 = {"gauges": {"serve.queue_depth": 0.0}, "counters": {},
             "histograms": {"serve.latency_s": dict(hist)}}
    d = ctl.evaluate(snap2)
    assert d.defer is False
    ctl.deferring = d.defer          # (tick would commit the release)
    # a fresh burst of fast latencies in the window stays un-hot
    hist3 = {"buckets": {"0.25": 3, "256.0": 8, "inf": 8}, "count": 8}
    snap3 = {"gauges": {"serve.queue_depth": 1.0}, "counters": {},
             "histograms": {"serve.latency_s": hist3}}
    assert ctl.evaluate(snap3).defer is False


def test_latency_quantile_and_read_inputs():
    hist = {"buckets": {"0.25": 4, "0.5": 9, "1.0": 10, "inf": 10},
            "count": 10}
    assert latency_quantile(hist, 0.4) == 0.25   # cum 4 covers 4.0
    assert latency_quantile(hist, 0.5) == 0.5    # cum 4 < 5 -> next edge
    assert latency_quantile(hist, 0.99) == 1.0
    assert latency_quantile({"buckets": {}, "count": 0}, 0.99) == 0.0
    snap = {"gauges": {"serve.queue_depth": 2.0,
                       "serve.occupancy.64x192": 1.0,
                       "serve.slots.64x192": 2.0,
                       "serve.admit_blocked.64x192": 1.0},
            "counters": {"tenant:a/serve.slo_violation": 2.0,
                         "serve.slo_violation": 1.0},
            "histograms": {"serve.latency_s": hist}}
    got = read_inputs(snap, deferring=True)
    assert got == {"queue_depth": 2, "occupancy": {"64x192": 1},
                   "slots": {"64x192": 2}, "blocked": {"64x192": 1},
                   "p99_s": 1.0, "slo_violations": 3.0,
                   "deferring": True}


def test_autoscale_tick_actuates_on_a_real_pool():
    from parmmg_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    pool = SlotPool(slots_per_bucket=1)
    pool.admit("a", 27, 48)
    key = pool._where["a"][0]
    label = pool.bucket_label(key)
    reg.gauge("serve.queue_depth").set(1)
    reg.gauge(f"serve.occupancy.{label}").set(1)
    reg.gauge(f"serve.slots.{label}").set(1)
    reg.gauge(f"serve.admit_blocked.{label}").set(1)
    ctl = AutoscaleController(max_slots=4, max_queue=0, target_p99_s=0,
                              shrink_after=2)
    d = ctl.tick(pool, registry=reg)
    assert d.grow == {label: 2}
    assert pool.buckets[key].nslots == 2 and ctl.grows == 1
    assert reg.snapshot()["counters"]["serve.autoscale.grow"] == 1
    # idle long enough -> shrink back (debounced over 2 evaluations)
    pool.release("a")
    reg.gauge("serve.queue_depth").set(0)
    reg.gauge(f"serve.occupancy.{label}").set(0)
    reg.gauge(f"serve.slots.{label}").set(2)
    reg.gauge(f"serve.admit_blocked.{label}").set(0)
    assert ctl.tick(pool, registry=reg).shrink == {}       # streak 1
    assert ctl.tick(pool, registry=reg).shrink == {}       # streak 2
    d = ctl.tick(pool, registry=reg)                       # acts
    assert d.shrink == {label: 1}
    assert pool.buckets[key].nslots == 1 and ctl.shrinks == 1


def test_autoscale_idle_shrink_reaches_floor_without_step():
    """tick() refreshes occupancy/slots gauges from the POOL: an idle
    pool (step never runs, so step's gauge publishing never fires)
    still shrinks rung by rung to the 1-slot floor — frozen gauges
    must not pin nslots at (last-gauged - 1) forever."""
    from parmmg_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    pool = SlotPool(slots_per_bucket=1)
    pool.admit("a", 27, 48)
    key = pool._where["a"][0]
    pool.release("a")
    pool.resize_bucket(key, 4)
    ctl = AutoscaleController(max_slots=8, shrink_after=1)
    for _ in range(8):
        ctl.tick(pool, registry=reg)
    assert pool.buckets[key].nslots == 1
    assert ctl.shrinks == 3


# ---------------------------------------------------------------------------
# pool resize + admission backpressure (host bookkeeping)
# ---------------------------------------------------------------------------
def test_resize_bucket_grow_and_trailing_free_shrink():
    pool = SlotPool(slots_per_bucket=2)
    pool.admit("a", 27, 48)
    key = pool._where["a"][0]
    assert pool.resize_bucket(key, 4) == 4
    assert pool.buckets[key].free_slot() == 1
    # tenant in slot 0: shrink keeps it, drops only trailing free slots
    assert pool.resize_bucket(key, 1) == 1
    assert pool.slot_of("a").tenant == "a"
    # a mid-array tenant blocks shrink below its own slot index + 1
    assert pool.resize_bucket(key, 3) == 3
    pool.admit("b", 27, 48)
    pool.admit("c", 27, 48)
    pool.release("b")                  # slot 1 free, slot 2 rented
    assert pool.resize_bucket(key, 1) == 3
    assert pool.labels() == {pool.bucket_label(key): key}


def test_try_submit_backpressure_and_latch():
    drv = StubDriver(pool=InstantPool(slots_per_bucket=1),
                     autoscale=False, max_queue=1, stream=True)
    vert, tet, met = _stub_mesh()
    tid, reason = drv.try_submit(mesh={"vert": vert, "tet": tet},
                                 met=met, tenant="q1")
    assert tid == "q1" and reason is None
    tid, reason = drv.try_submit(mesh={"vert": vert, "tet": tet},
                                 met=met, tenant="q2")
    assert tid is None and "queue full" in reason
    assert drv.admission.deferred == 1 and "q2" not in drv.requests
    # the autoscale defer latch blocks even an empty queue
    drv.queue = []
    drv.admission.deferring = True
    tid, reason = drv.try_submit(mesh={"vert": vert, "tet": tet},
                                 met=met, tenant="q3")
    assert tid is None and "autoscale" in reason


def test_stream_midstep_rerent(monkeypatch):
    """The REAL SlotPool.step streaming worklist: a slot freed by a
    cohort's retirement is re-rented to a queued tenant and dispatched
    at its own cycle 0 within the SAME pool step (3 tenants through 1
    slot in ONE step; the cohort dispatch itself is stubbed so the
    test stays host-only)."""
    calls = []

    def fake_dispatch(self, b, fn, wave, ids, done):
        calls.append([b.slots[i].tenant for i in ids])
        self.dispatches += len(ids)
        return [(i, np.zeros(8, np.int64)) for i in ids]

    monkeypatch.setattr(SlotPool, "_dispatch_cohort", fake_dispatch)
    vert, tet, met = _stub_mesh()
    drv = StubDriver(pool=HostPool(slots_per_bucket=1, cycles=1),
                     autoscale=False, stream=True)
    for t in ("ra", "rb", "rc"):
        drv.submit(mesh={"vert": vert, "tet": tet}, met=met, tenant=t)
    rep = drv.run()
    assert rep["served"] == 3 and rep["failed"] == 0
    # ONE pool step served all three through the one slot: each
    # retirement re-rented the slot mid-step (the zero-count block is a
    # converged fixed point, so every tenant retires on its dispatch)
    assert drv.pool.steps == 1
    assert calls == [["ra"], ["rb"], ["rc"]]
    assert rep["admission"]["stream_admissions"] == 2
    # between-steps mode on the same workload pays one step per tenant
    monkeypatch.setattr(SlotPool, "_dispatch_cohort", fake_dispatch)
    drv2 = StubDriver(pool=HostPool(slots_per_bucket=1, cycles=1),
                      autoscale=False, stream=False)
    for t in ("sa", "sb", "sc"):
        drv2.submit(mesh={"vert": vert, "tet": tet}, met=met, tenant=t)
    rep2 = drv2.run()
    assert rep2["served"] == 3 and drv2.pool.steps == 3
    assert rep2["admission"]["stream_admissions"] == 0


# ---------------------------------------------------------------------------
# daemon lifecycle over localhost (stub pool, host-only)
# ---------------------------------------------------------------------------
def test_daemon_lifecycle_roundtrip_stub_pool():
    d = _daemon()
    try:
        cl = ServeClient(port=d.port, timeout_s=10)
        h = cl.health()
        assert h["ok"] is True and h["paused"] is True
        vert, tet, met = _stub_mesh()
        tid = cl.submit(vert=vert, tet=tet, met=met, tenant="stub-a")
        assert tid == "stub-a"
        assert cl.poll(tid)["state"] == "queued"
        assert cl.step()["state"] == "active"   # manual loop iteration
        got = cl.wait(tid, timeout_s=5)
        assert got["state"] == "done" and got["quality"]["qmin"] == 1.0
        arrays = cl.fetch(tid)                  # bit-exact npz roundtrip
        assert (arrays["vert"] == vert).all()
        assert (arrays["tet"] == tet).all()
        assert (arrays["met"] == met).all()
        # a second tenant rides the LIVE loop after /resume
        cl.resume()
        tid2 = cl.submit(vert=vert, tet=tet, met=met)
        assert cl.wait(tid2, timeout_s=5)["state"] == "done"
        cl.pause()
        from parmmg_tpu.obs.metrics import parse_prometheus
        series = parse_prometheus(cl.metrics_text())
        assert any(n == "parmmg_serve_admit_ok_total"
                   for n, _ in series)
        with pytest.raises(ServeDaemonError) as ei:
            cl.poll("no-such-request")
        assert ei.value.status == 404
        rep = cl.report()
        assert rep["served"] == 2 and rep["failed"] == 0
    finally:
        d.shutdown()
    assert not d.alive()


def test_daemon_rpc_fault_quarantines_midflight(monkeypatch):
    """The serve.daemon_rpc faultpoint: an RPC fault on a RUNNING
    tenant's request quarantines THAT tenant (retired FAILED, slot
    recycled) while the daemon and its cohort-mates keep going — the
    tier-1 mirror of the --chaos daemon scenario."""
    from parmmg_tpu.resilience.faults import FAULTS
    d = _daemon(steps=2)
    try:
        cl = ServeClient(port=d.port, timeout_s=10)
        vert, tet, met = _stub_mesh()
        for t in ("fa", "fb"):
            cl.submit(vert=vert, tet=tet, met=met, tenant=t)
        cl.step()                       # both advance 1 of 2 steps
        assert cl.poll("fa")["state"] == "running"
        monkeypatch.setenv("PARMMG_FAULT", "serve.daemon_rpc:key=fa")
        FAULTS.reset()
        with pytest.raises(ServeDaemonError) as ei:
            cl.poll("fa")
        assert ei.value.status == 500
        assert ei.value.body["quarantined"] is True
        monkeypatch.delenv("PARMMG_FAULT")
        FAULTS.reset()
        assert cl.health()["ok"] is True            # daemon survives
        assert cl.poll("fa")["state"] == "failed"
        cl.step()
        assert cl.wait("fb", timeout_s=5)["state"] == "done"
        rep = cl.report()
        assert "fa" in rep["pool"]["quarantined"]
        assert "daemon rpc fault" in rep["tenants"]["fa"]["reason"]
        # the quarantined tenant's slot is back on the free list
        occ = rep["pool"]["buckets"]
        assert all(used == 0 for used, _ in occ.values())
    finally:
        monkeypatch.delenv("PARMMG_FAULT", raising=False)
        FAULTS.reset()
        d.shutdown()


def test_terminal_request_eviction_bounds_the_table():
    """A persistent service must not retain every finished request's
    merged mesh forever: beyond ``retain_done``, the oldest terminal
    requests are evicted (in-flight ones never are)."""
    vert, tet, met = _stub_mesh()
    drv = StubDriver(pool=InstantPool(slots_per_bucket=2),
                     autoscale=False, retain_done=2)
    for t in ("e0", "e1", "e2", "e3"):
        drv.submit(mesh={"vert": vert, "tet": tet}, met=met, tenant=t)
    rep = drv.run()
    # the two OLDEST finished requests were evicted; the table (and the
    # report, which covers retained requests only — the bounded-history
    # contract) holds exactly retain_done entries, all slots recycled
    assert len(drv.requests) == 2
    assert set(drv.requests) == {"e2", "e3"}
    assert rep["served"] == 2
    assert all(used == 0 for used, _ in drv.pool.occupancy().values())
    assert drv.fetch("e3") is not None
    with pytest.raises(KeyError):
        drv.poll("e0")


class FlakyDriver(StubDriver):
    """service_once raises a few times before recovering — the daemon
    loop-guard fixture."""

    boom = 2

    def service_once(self):
        if self.boom and self.queue:
            self.boom -= 1
            raise RuntimeError("injected loop iteration failure")
        return super().service_once()


def test_daemon_loop_survives_iteration_errors():
    """An exception escaping one serving-loop iteration must not kill
    the loop thread: the daemon accounts it, keeps looping, and still
    serves — and /healthz reports loop liveness honestly."""
    from parmmg_tpu.obs.metrics import REGISTRY
    pool = InstantPool(steps_to_converge=1, slots_per_bucket=2)
    drv = FlakyDriver(pool=pool, autoscale=False)
    d = PoolDaemon(driver=drv, port=0, idle_sleep_s=0.005).start()
    try:
        c0 = REGISTRY.counter("serve.loop_errors").value
        cl = ServeClient(port=d.port, timeout_s=10)
        vert, tet, met = _stub_mesh()
        tid = cl.submit(vert=vert, tet=tet, met=met, tenant="flaky")
        assert cl.wait(tid, timeout_s=5)["state"] == "done"
        assert REGISTRY.counter("serve.loop_errors").value - c0 == 2
        h = cl.health()
        assert h["ok"] is True and h["loop_alive"] is True
    finally:
        d.shutdown()


def test_daemon_malformed_submit_is_500_not_404():
    """A submit payload missing required arrays is a server-side 500
    (counted in serve.rpc_errors), never a 404 'unknown request'."""
    import base64
    import io
    d = _daemon()
    try:
        cl = ServeClient(port=d.port, timeout_s=10)
        buf = io.BytesIO()
        np.savez_compressed(buf, tet=np.zeros((1, 4), np.int32))
        with pytest.raises(ServeDaemonError) as ei:
            cl._rpc("POST", "/submit", {
                "npz_b64": base64.b64encode(buf.getvalue())
                .decode("ascii")})
        assert ei.value.status == 500
        assert "vert" in str(ei.value.body)
    finally:
        d.shutdown()


def test_daemon_backpressure_429():
    d = _daemon(max_queue=1)
    try:
        cl = ServeClient(port=d.port, timeout_s=10)
        vert, tet, met = _stub_mesh()
        cl.submit(vert=vert, tet=tet, met=met, tenant="bp1")
        with pytest.raises(BackpressureDeferred) as ei:
            cl.submit(vert=vert, tet=tet, met=met, tenant="bp2")
        assert ei.value.status == 429
        assert ei.value.body["deferred"] is True
        with pytest.raises(ServeDaemonError):   # bp2 never enqueued
            cl.poll("bp2")
        # the queued tenant drains -> the SAME submit now lands
        cl.step()
        assert cl.wait("bp1", timeout_s=5)["state"] == "done"
        assert cl.submit(vert=vert, tet=tet, met=met,
                         tenant="bp2") == "bp2"
    finally:
        d.shutdown()


# ---------------------------------------------------------------------------
# streaming-admission exactness (slow tier: group-block XLA compiles)
# ---------------------------------------------------------------------------
def _tenant(n=2, h=0.55):
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.utils.fixtures import cube_mesh
    vert, tet = cube_mesh(n)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, h, m.vert.dtype)
    return m, met


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_streaming_admission_bit_parity():
    """Streaming (mid-step re-rent) vs between-steps admission: every
    tenant retires bit-for-bit identical — admission TIMING never
    changes a tenant's bytes.  3 tenants of distinct metrics through
    ONE home slot, so the streaming run genuinely re-rents mid-step."""
    from parmmg_tpu.core.mesh import MESH_FIELDS
    cases = {"pa": 0.55, "pb": 0.42, "pc": 0.5}
    outs = {}
    for stream in (False, True):
        drv = ServeDriver(slots_per_bucket=1, chunk=1, cycles=3,
                          stream=stream, autoscale=False)
        for tid, h in cases.items():
            m, met = _tenant(2, h)
            drv.submit(mesh=m, met=met, tenant=tid)
        rep = drv.run()
        assert rep["served"] == 3 and rep["failed"] == 0
        outs[stream] = {
            tid: tuple(np.asarray(getattr(drv.fetch(tid)[0], f))
                       .tobytes() for f in MESH_FIELDS)
            + (np.asarray(drv.fetch(tid)[1]).tobytes(),)
            for tid in cases}
        if stream:
            assert rep["admission"]["stream_admissions"] >= 1
    assert outs[False] == outs[True]
