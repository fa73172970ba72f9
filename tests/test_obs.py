"""Telemetry spine (parmmg_tpu/obs): trace, metrics, artifacts.

All host-only — no jitted programs, so tier-1 pays zero compile time
for this file.  The compile-family and replay-parity end-to-end gates
live in scripts/obs_check.py (run_tests.sh --obs); here the host
semantics: span nesting + run-context propagation, the Timers bridge
(emission parity, external-segment tagging), histogram bucket edges,
Prometheus exposition round-trip, tenant namespacing riding the
AdaptStats isolation contract, and artifact schema validation on the
checked-in BENCH/SCALE/SERVE round artifacts.
"""
import json
import os

import pytest

from parmmg_tpu.obs import artifact as oart
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.obs.metrics import (DEFAULT_BUCKETS, MetricsRegistry,
                                    parse_prometheus, publish_stats)
from parmmg_tpu.ops.adapt import AdaptStats
from parmmg_tpu.utils.timers import Timers

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture()
def fresh_tracer():
    """Route the global tracer (Timers emits into it) at a clean ring,
    no file sink; restore the env-driven default afterwards."""
    otrace.TRACER.configure(path=None)
    otrace.TRACER.reset()
    yield otrace.TRACER
    otrace.TRACER.configure(path=None)
    otrace.TRACER.reset()


def spans(tracer, **match):
    out = []
    for r in list(tracer.ring):
        if r.get("kind") != "span":
            continue
        if all(r.get(k) == v for k, v in match.items()):
            out.append(r)
    return out


# ---------------------------------------------------------------------------
# trace: spans, context, log
# ---------------------------------------------------------------------------
def test_span_nesting_and_context_propagation(fresh_tracer):
    rid = otrace.new_run(backend="cpu")
    with otrace.context(**{"pass": 2, "tenant": "t0"}):
        with otrace.span("outer"):
            with otrace.context(block=3):
                with otrace.span("inner"):
                    pass
    recs = spans(fresh_tracer)
    names = [r["name"] for r in recs]
    # inner completes (and therefore emits) before outer
    assert names == ["inner", "outer"]
    inner, outer = recs
    # run context folded into every record; scoped overlay only inside
    for r in (inner, outer):
        assert r["run"] == rid and r["backend"] == "cpu"
        assert r["pass"] == 2 and r["tenant"] == "t0"
    assert inner["block"] == 3 and "block" not in outer
    # leaving the scopes clears the overlay
    otrace.event("after")
    after = [r for r in fresh_tracer.ring if r.get("name") == "after"][0]
    assert "pass" not in after and "block" not in after
    otrace.new_run()  # don't leak tenant/backend into other tests


def test_timers_emit_and_replay_exactly(fresh_tracer):
    tim = Timers()
    with tim("a"):
        with tim("b"):
            pass
    with tim("a"):
        pass
    tim.add("c", 0.5, count=3)          # root-level absorb
    tot, cnt = otrace.replay_totals(list(fresh_tracer.ring),
                                    tim=tim.trace_id)
    assert set(tot) == set(tim.acc) == {"a", "a/b", "c"}
    for k in tim.acc:
        assert tot[k] == pytest.approx(tim.acc[k], rel=1e-12)
        assert cnt[k] == tim.count[k]
    # a second instance's spans don't bleed into the replay
    other = Timers()
    with other("a"):
        pass
    tot2, _ = otrace.replay_totals(list(fresh_tracer.ring),
                                   tim=tim.trace_id)
    assert tot2["a"] == pytest.approx(tim.acc["a"], rel=1e-12)


def test_timers_add_external_tagging(fresh_tracer):
    tim = Timers()
    with tim("phase"):
        tim.add("seg", 0.25)            # inside a scope: a sub-segment
    tim.add("orphan", 1.0)              # outside any scope: external
    assert "phase/seg" in tim.acc and "phase/seg" not in tim.external
    assert "orphan" in tim.external
    rep = tim.report()
    orphan_line = [ln for ln in rep.splitlines() if "orphan" in ln][0]
    seg_line = [ln for ln in rep.splitlines() if "seg" in ln][0]
    assert "[absorbed]" in orphan_line
    assert "[absorbed]" not in seg_line
    ext = spans(fresh_tracer, name="orphan")[0]
    assert ext.get("ext") is True
    assert not spans(fresh_tracer, name="phase/seg")[0].get("ext")


def test_log_gates_but_always_traces(fresh_tracer, capsys):
    assert otrace.log(2, "visible", verbose=3) is True
    assert otrace.log(3, "hidden", verbose=2) is False
    out = capsys.readouterr().out
    assert "visible" in out and "hidden" not in out
    logs = [r for r in fresh_tracer.ring if r.get("kind") == "log"]
    assert [r["msg"] for r in logs] == ["visible", "hidden"]
    assert logs[0]["shown"] is True and logs[1]["shown"] is False


def test_jsonl_sink_and_file_replay(tmp_path, fresh_tracer):
    path = str(tmp_path / "trace.jsonl")
    otrace.TRACER.configure(path=path)
    tim = Timers()
    with tim("x"):
        with tim("y"):
            pass
    otrace.event("marker", foo=1)
    otrace.TRACER.configure(path=None)
    recs = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert all("ts" in r for r in recs)
    assert any(r.get("name") == "marker" and r.get("foo") == 1
               for r in recs)
    tot, cnt = otrace.replay_totals(path, tim=tim.trace_id)
    assert set(tot) == {"x", "x/y"}
    assert tot["x"] == pytest.approx(tim.acc["x"], rel=1e-12)
    assert cnt["x/y"] == 1


def test_tracer_ring_bound_and_summary():
    t = otrace.Tracer(ring=4, path=None)
    for i in range(10):
        t.emit({"kind": "span", "name": f"s{i % 2}", "dur": 0.1})
    s = t.summary()
    assert s["events"] == 10 and s["ring"] == 4 and s["dropped"] == 6
    assert set(s["top_spans_s"]) <= {"s0", "s1"}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_histogram_bucket_edges():
    reg = MetricsRegistry()
    h = reg.histogram("lat", bounds=(0.1, 1.0, 10.0))
    # le bounds are INCLUSIVE upper edges (Prometheus convention)
    h.observe(0.1)        # == first bound -> first bucket
    h.observe(0.100001)   # just past    -> second bucket
    h.observe(1.0)        # == second    -> second bucket
    h.observe(10.0)       # == last      -> third bucket
    h.observe(11.0)       # past all     -> +Inf bucket
    assert h.counts == [1, 2, 1, 1]
    cum = dict(h.cumulative())
    assert cum[0.1] == 1 and cum[1.0] == 3 and cum[10.0] == 4
    assert cum[float("inf")] == 5
    assert h.n == 5 and h.sum == pytest.approx(22.200001)
    # default ladder is fixed, increasing, log-spaced
    assert all(b2 / b1 == 2.0
               for b1, b2 in zip(DEFAULT_BUCKETS, DEFAULT_BUCKETS[1:]))


def test_metrics_registry_types_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2)
    reg.counter("a.b").inc(0.5)         # same series accumulates
    reg.gauge("g").set(7)
    reg.histogram("h").observe(0.01)
    with pytest.raises(TypeError):
        reg.gauge("a.b")                # kind collision
    with pytest.raises(ValueError):
        reg.counter("a.b").inc(-1)      # counters are monotone
    snap = reg.snapshot()
    assert snap["counters"]["a.b"] == 2.5
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["h"]["count"] == 1
    json.dumps(snap)                    # JSON-serializable


def test_prometheus_exposition_roundtrip():
    reg = MetricsRegistry()
    reg.counter("serve.admit_ok").inc(3)
    reg.counter("adapt.nsplit", tenant="t-1").inc(41)
    reg.gauge("serve.queue_depth").set(2)
    h = reg.histogram("serve.latency_s", bounds=(0.5, 2.0))
    h.observe(0.4)
    h.observe(1.7)
    h.observe(9.0)
    text = reg.to_prometheus()
    parsed = parse_prometheus(text)
    assert parsed[("parmmg_serve_admit_ok_total", frozenset())] == 3
    assert parsed[("parmmg_adapt_nsplit_total",
                   frozenset({("tenant", "t-1")}))] == 41
    assert parsed[("parmmg_serve_queue_depth", frozenset())] == 2
    assert parsed[("parmmg_serve_latency_s_bucket",
                   frozenset({("le", "0.5")}))] == 1
    assert parsed[("parmmg_serve_latency_s_bucket",
                   frozenset({("le", "2")}))] == 2
    assert parsed[("parmmg_serve_latency_s_bucket",
                   frozenset({("le", "+Inf")}))] == 3
    assert parsed[("parmmg_serve_latency_s_count", frozenset())] == 3
    assert parsed[("parmmg_serve_latency_s_sum",
                   frozenset())] == pytest.approx(11.1)


def test_tenant_namespacing_rides_adaptstats_isolation():
    # cross-tenant AdaptStats merge STILL raises (the isolation
    # contract the metrics bridge relies on)
    a = AdaptStats(tenant="a", nsplit=1)
    b = AdaptStats(tenant="b", nsplit=2)
    with pytest.raises(ValueError):
        a += b
    reg = MetricsRegistry()
    publish_stats(a, reg)
    publish_stats(b, reg)
    agg = AdaptStats()
    agg += AdaptStats(tenant="c", nsplit=5,
                      sched_extra={"grp_upload_s": 0.5})
    publish_stats(agg, reg)
    snap = reg.snapshot()["counters"]
    # the AdaptStats tenant:<id>/ namespacing convention, per series
    assert snap["tenant:a/adapt.nsplit"] == 1
    assert snap["tenant:b/adapt.nsplit"] == 2
    assert snap["adapt.nsplit"] == 5          # untagged aggregate
    # the aggregate's absorbed per-tenant keys keep their namespacing
    # instead of double-tagging (they are already tenant:<id>/-scoped)
    assert "sched.tenant:c/grp_upload_s" not in snap
    # exposition separates the tenants as labels
    parsed = parse_prometheus(reg.to_prometheus())
    assert parsed[("parmmg_adapt_nsplit_total",
                   frozenset({("tenant", "a")}))] == 1
    assert parsed[("parmmg_adapt_nsplit_total",
                   frozenset({("tenant", "b")}))] == 2


# ---------------------------------------------------------------------------
# artifacts
# ---------------------------------------------------------------------------
def test_make_artifact_is_canonical_and_valid(fresh_tracer):
    reg = MetricsRegistry()
    reg.counter("x").inc()
    doc = oart.make_artifact("BENCH", metric="m", value=1.5, unit="u",
                             extra={"qmin": 0.3}, vs_baseline=2.0,
                             registry=reg)
    assert oart.validate_artifact(doc) == []
    assert doc["schema_version"] == oart.SCHEMA_VERSION
    assert doc["metrics"]["counters"]["x"] == 1.0
    assert "compile_ledger" in doc["extra"]
    assert "backend" in doc["env"]
    json.dumps(doc)
    # the upgrade path is a no-op on canonical docs
    assert oart.upgrade_artifact(doc) is doc
    with pytest.raises(ValueError):
        oart.make_artifact("NOPE", metric="m", value=0, unit="")


@pytest.mark.parametrize("fname", ["BENCH_r06.json", "SCALE_r03.json",
                                   "SERVE_r01.json"])
def test_checked_in_artifacts_upgrade_and_validate(fname):
    with open(os.path.join(ROOT, fname)) as f:
        doc = json.load(f)
    up = oart.upgrade_artifact(doc)
    assert oart.validate_artifact(up) == [], fname
    kind = fname.split("_")[0]
    assert up["kind"] == kind
    assert up["value"] > 0
    json.dumps(up)


def test_validate_rejects_malformed():
    assert oart.validate_artifact([]) != []
    doc = oart.make_artifact("SCALE", metric="m", value=1.0, unit="u")
    bad = dict(doc)
    bad.pop("metrics")
    assert any("metrics" in p for p in oart.validate_artifact(bad))
    bad2 = dict(doc, kind="WHAT")
    assert any("kind" in p for p in oart.validate_artifact(bad2))
    bad3 = dict(doc, value="fast")
    assert any("value" in p for p in oart.validate_artifact(bad3))


def test_artifact_diff_ledger_value_and_metrics():
    def mk(variants, value, qmin, counters):
        return {"schema_version": 1, "kind": "BENCH", "metric": "thr",
                "value": value, "unit": "u", "env": {"backend": "cpu"},
                "metrics": {"counters": counters, "gauges": {},
                            "histograms": {}},
                "trace": {"events": 0},
                "extra": {"qmin": qmin, "compile_ledger": {
                    "groups.adapt_block": {"variants": variants}}}}

    old = mk(1, 1.0, 0.30, {"groups.dispatches": 5})
    # ledger growth + throughput drop + qmin drop + vanished counter
    new = mk(3, 0.5, 0.10, {})
    d = oart.artifact_diff(old, new)
    assert any("groups.adapt_block" in v for v in d["ledger"])
    assert any("thr" in v for v in d["value"])
    assert any("qmin" in v for v in d["quality"])
    assert any("groups.dispatches" in v for v in d["notes"])
    # improvement directions stay quiet
    better = mk(1, 2.0, 0.35, {"groups.dispatches": 9})
    d2 = oart.artifact_diff(old, better)
    assert d2["ledger"] == [] and d2["value"] == [] \
        and d2["quality"] == [] and d2["notes"] == []


def test_artifact_diff_direction_for_seconds_metrics():
    # seconds-valued headline (MULTIHOST wall time): regression is UP
    def mh(seconds):
        return {"schema_version": 1, "kind": "MULTIHOST",
                "metric": "multihost_adapt", "value": seconds,
                "unit": "s", "env": {"backend": "cpu"},
                "metrics": {"counters": {}, "gauges": {},
                            "histograms": {}},
                "trace": {"events": 0},
                "extra": {"compile_ledger": {}}}

    faster = oart.artifact_diff(mh(100.0), mh(80.0))
    assert faster["value"] == []          # 20% faster is NOT a regression
    slower = oart.artifact_diff(mh(100.0), mh(200.0))
    assert any("multihost_adapt" in v for v in slower["value"])


def test_artifact_diff_on_checked_in_rounds():
    # the real r04 -> r06 bench history (CPU-backend builder artifacts)
    # must not flag ledger regressions, and must report the throughput
    # drop those rounds carry (0.1829 -> 0.1336)
    with open(os.path.join(ROOT, "BENCH_r04.json")) as f:
        old = json.load(f)
    with open(os.path.join(ROOT, "BENCH_r06.json")) as f:
        new = json.load(f)
    d = oart.artifact_diff(old, new)
    assert d["ledger"] == []
    assert len(d["value"]) == 1 and "adapt_cycle_throughput" in d["value"][0]
    # same round against itself: nothing to report
    assert oart.artifact_diff(new, new)["value"] == []


def test_profiler_unarmed_is_inert(monkeypatch, fresh_tracer):
    monkeypatch.delenv("PARMMG_PROFILE_DIR", raising=False)
    assert otrace.profile_pass_begin(0) is False
    assert otrace.profile_pass_end(0) is False
    assert otrace.profiling_active() is False
    # annotate/scope degrade to free nullcontexts when inert
    with otrace.annotate("x"):
        with otrace.scope("y"):
            pass
