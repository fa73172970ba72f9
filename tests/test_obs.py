"""Telemetry spine (parmmg_tpu/obs): trace, metrics, artifacts.

Host-only but for one tiny grouped job (the ``grouped_job`` fixture:
``cube_mesh(3)``, two groups, compiled once for the module, about a
minute on the CPU) whose span tree, counters and operator capture the
last tests read.  The compile-family and replay-parity end-to-end gates
live in scripts/obs_check.py (run_tests.sh --obs); here the host
semantics: the span primitive (identity, parent, the shared clock) +
run-context propagation, the Timers bridge (emission parity,
external-segment tagging), the compile listener's attribution,
histogram bucket edges, Prometheus exposition round-trip, tenant
namespacing riding the AdaptStats isolation contract, and artifact
schema validation on the checked-in BENCH/SCALE/SERVE round artifacts.
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


# ---------------------------------------------------------------------------
# the span primitive: identity, the shared clock, the job's span tree
# ---------------------------------------------------------------------------
def _interval(r):
    return r["t0"], r["t0"] + round(r["dur"] * 1e9)


def test_spans_carry_identity_and_nest_in_time(fresh_tracer):
    rid = otrace.new_run(backend="cpu")
    with otrace.span("outer") as outer:
        assert otrace.current_span() == outer.id
        with otrace.span("inner", wave=3) as inner:
            inner.set(collapse=2, swap=1)
        otrace.event("mark")
    assert otrace.current_span() is None
    ri, ro = spans(fresh_tracer)
    assert (ri["name"], ro["name"]) == ("inner", "outer")
    assert ri["id"] != ro["id"] and ri["parent"] == ro["id"]
    assert "parent" not in ro                       # a root
    assert ri["run"] == ro["run"] == rid
    assert (ri["wave"], ri["collapse"], ri["swap"]) == (3, 2, 1)
    (i0, i1), (o0, o1) = _interval(ri), _interval(ro)
    assert o0 <= i0 <= i1 <= o1                     # one clock, nested
    assert inner.dur == ri["dur"] and outer.dur == ro["dur"]
    mark = [r for r in fresh_tracer.ring if r.get("name") == "mark"][0]
    assert mark["parent"] == ro["id"]
    otrace.new_run()


def test_timers_go_through_the_span_primitive(fresh_tracer):
    tim = Timers()
    with otrace.span("root") as root:
        with tim("a"):
            with tim("b"):
                tim.add("seg", 0.25, count=2)
        tim.add("ext", 1.5)
    recs = {r["name"]: r for r in spans(fresh_tracer)}
    assert set(recs) == {"root", "a", "a/b", "a/b/seg", "ext"}
    assert recs["a"]["parent"] == root.id
    assert recs["a/b"]["parent"] == recs["a"]["id"]
    assert recs["a/b/seg"]["parent"] == recs["a/b"]["id"]
    assert recs["ext"]["parent"] == root.id
    # scopes have a start on the clock, folded-in durations have none
    assert "t0" in recs["a"] and "t0" in recs["a/b"]
    assert "t0" not in recs["a/b/seg"] and "t0" not in recs["ext"]
    assert recs["ext"].get("ext") is True and "ext" not in recs["a/b/seg"]
    assert all(recs[n]["tim"] == tim.trace_id
               for n in ("a", "a/b", "a/b/seg", "ext"))
    assert len({r["id"] for r in recs.values()}) == 5
    # and the stream still replays to the registry exactly
    tot, cnt = otrace.replay_totals(list(fresh_tracer.ring),
                                    tim=tim.trace_id)
    assert tot == tim.acc and cnt == tim.count
    assert cnt["a/b/seg"] == 2


def test_span_records_without_jax():
    """Host-only contexts stay jax-free: the primitive neither imports
    jax nor needs it."""
    import subprocess
    import sys
    code = (
        "import sys\n"
        "from parmmg_tpu.obs import trace as t\n"
        "from parmmg_tpu.utils.timers import Timers\n"
        "tim = Timers()\n"
        "with t.span('a', n=1) as a:\n"
        "    with tim('b'):\n"
        "        pass\n"
        "recs = [r for r in t.TRACER.ring if r['kind'] == 'span']\n"
        "assert [r['name'] for r in recs] == ['b', 'a'], recs\n"
        "assert recs[0]['parent'] == recs[1]['id'] == a.id\n"
        "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


def test_compile_listener_names_the_span_that_paid(fresh_tracer):
    import jax
    import numpy as np
    from parmmg_tpu.obs.metrics import REGISTRY
    from parmmg_tpu.utils.compilecache import LEDGER
    LEDGER.install_listener()

    def count(name):
        return REGISTRY.snapshot()["counters"].get(name, 0.0)

    x = np.arange(7, dtype=np.float32)          # no eager jnp op
    n0, s0, t0 = (count("compile.backend_n"), count("compile.backend_s"),
                  count("compile.trace_lower_s"))
    fresh = jax.jit(lambda v: v * 3.0 + 1.0)
    with otrace.span("step") as step:
        fresh(x).block_until_ready()
    assert count("compile.backend_n") == n0 + 1
    assert count("compile.backend_s") > s0
    assert count("compile.trace_lower_s") > t0
    ev = [r for r in fresh_tracer.ring if r.get("name") == "compile"]
    assert len(ev) == 1 and ev[0]["kind"] == "event"
    assert ev[0]["parent"] == step.id
    assert ev[0]["fun"].startswith("jit(") and ev[0]["dur"] > 0
    with otrace.span("again"):
        fresh(x).block_until_ready()            # compiled: no event
    assert count("compile.backend_n") == n0 + 1


def test_nested_compile_events_are_not_counted_twice():
    """jax reports an inner event inside the one round it; the counters
    add up to the time spent."""
    from parmmg_tpu.utils.compilecache import CompileLedger
    led = CompileLedger()
    assert led._exclusive(0.25) == pytest.approx(0.25)      # inner
    import time
    time.sleep(0.01)
    assert led._exclusive(1.0) == pytest.approx(0.75, abs=1e-3)  # outer
    assert led._exclusive(0.0) == 0.0                       # a later one


# ---- one tiny grouped job through the public API (cube_mesh(3), two
# groups, two passes): compiles once for the module, a minute on the CPU
GROUPED_SPANS = {
    # name: its parent's name (None: the root of the job)
    "run": None, "analysis": "run", "metric": "run", "backup": "run",
    "adaptation": "run", "bad-element polish": "run",
    "sequential repair": "run", "fem conformity": "run",
    "grp split": "adaptation", "grp upload": "adaptation",
    "grp block": "adaptation", "grp pull": "adaptation",
    "grp merge": "adaptation", "grp displace": "adaptation",
    "grp checkpoint": "adaptation", "adaptation/grp compute": "adaptation",
    "polish wave": "bad-element polish", "fem round": "fem conformity",
}


@pytest.fixture(scope="module")
def grouped_job(tmp_path_factory):
    import numpy as np
    from parmmg_tpu.api.params import IParam
    from parmmg_tpu.api.parmesh import ParMesh
    from parmmg_tpu.obs.metrics import REGISTRY
    from parmmg_tpu.utils.fixtures import cube_mesh
    vert, tet = cube_mesh(3)

    def counters():
        return dict(REGISTRY.snapshot()["counters"])

    def job():
        otrace.TRACER.configure(path=None)
        otrace.TRACER.reset()
        before = counters()
        pm = ParMesh()
        pm.set_mesh_size(np_=len(vert), ne=len(tet))
        pm.set_vertices(vert)
        pm.set_tetrahedra(tet + 1)
        pm.set_met_size(1, len(vert))
        pm.set_scalar_mets(np.full(len(vert), 0.3))
        pm.set_iparameter(IParam.meshSize, len(tet) // 2)   # 2 groups
        pm.set_iparameter(IParam.niter, 2)
        pm.set_iparameter(IParam.verbose, -1)
        rc = pm.run()
        out_tet, _ = pm.get_tetrahedra()
        after = counters()
        return {"rc": rc, "ne_in": len(tet), "ne_out": len(out_tet),
                "records": list(otrace.TRACER.ring),
                "summary": otrace.TRACER.summary(),
                "counters": {k: after[k] - before.get(k, 0.0)
                             for k in after}}

    # what the map and the digest cost a job that did not ask for them:
    # count the calls of both (obs/devtime)
    from parmmg_tpu.obs import devtime
    asked = {"scope_map": 0, "digest": 0}

    def counting(name, inner):
        def call(*a, **k):
            asked[name] += 1
            return inner(*a, **k)
        return call

    mp = pytest.MonkeyPatch()
    try:
        for name in asked:
            mp.setattr(devtime, name, counting(name, getattr(devtime, name)))
        # the digest is what is tested here, not the guard against a
        # second cold compile: beside five other workers a toy program's
        # compile can pass the guard's 30 s, and its map is then refused
        mp.setattr(devtime, "COLD_COMPILE_LIMIT_S", float("inf"))
        # cold, with the pass checkpoints armed
        mp.setenv("PARMMG_CKPT_DIR", str(tmp_path_factory.mktemp("ckpt")))
        cold = job()
        cold["asked"] = dict(asked)
        mp.delenv("PARMMG_CKPT_DIR")
        # warm, inside the operator's capture
        prof = str(tmp_path_factory.mktemp("prof"))
        mp.setenv("PARMMG_PROFILE_DIR", prof)
        warm = job()
        warm["asked"] = dict(asked)
    finally:
        mp.undo()
        otrace.TRACER.reset()
    return {"cold": cold, "warm": warm, "profile_dir": prof}


def _tree(records):
    recs = [r for r in records if r.get("kind") == "span"]
    return recs, {r["id"]: r for r in recs}


def test_grouped_job_emits_the_span_tree(grouped_job):
    job = grouped_job["cold"]
    assert job["rc"] == 0
    recs, by_id = _tree(job["records"])
    names = {r["name"] for r in recs}
    assert set(GROUPED_SPANS) <= names, set(GROUPED_SPANS) - names
    for r in recs:
        if r["name"] not in GROUPED_SPANS:
            continue
        want = GROUPED_SPANS[r["name"]]
        got = by_id[r["parent"]]["name"] if "parent" in r else None
        assert got == want, (r["name"], got)
    assert len({r["run"] for r in recs}) == 1
    assert len(by_id) == len(recs)                  # ids are unique
    root = [r for r in recs if r["name"] == "run"]
    assert len(root) == 1
    assert root[0]["ne_in"] == job["ne_in"]
    assert root[0]["ne_out"] == job["ne_out"] and root[0]["status"] == 0
    # what the accepted readers count on: one folded record a pass
    assert sum(r["name"] == "adaptation/grp compute" for r in recs) == 2
    assert sum(r["name"] == "grp displace" for r in recs) == 1
    split = [r for r in recs if r["name"] == "grp split"]
    assert len(split) == 2 and all(
        r["groups"] == 2 and r["capT"] > 0 and r["largest"] > 0
        and r["pass"] == i for i, r in enumerate(split))
    blocks = [r for r in recs if r["name"] == "grp block"]
    assert job["counters"]["groups.dispatches"] == len(blocks)
    assert sum(r["dur"] for r in blocks) == pytest.approx(
        job["counters"]["groups.pipeline.compute_s"], rel=1e-9)
    assert all({"split", "collapse", "swap", "moved", "block",
                "active"} <= set(r) for r in blocks)
    assert sum(r["split"] for r in blocks) > 0
    assert job["counters"]["api.set_s"] > 0
    assert job["counters"]["api.get_s"] > 0


def test_polish_waves_count_what_they_applied(grouped_job):
    job = grouped_job["cold"]
    recs, _ = _tree(job["records"])
    waves = [r for r in recs if r["name"] == "polish wave"]
    assert [r["wave"] for r in waves] == list(range(len(waves)))
    assert job["counters"]["tail.polish_waves"] == len(waves)
    assert job["counters"]["tail.polish_ops"] == sum(
        r["collapse"] + r["swap"] for r in waves)
    # the loop stops on the first wave that applies nothing
    assert all(r["collapse"] + r["swap"] > 0 for r in waves[:-1])
    last = waves[-1]
    assert last["collapse"] + last["swap"] == 0 or len(waves) == 8


def test_polish_waves_say_which_stages_ran(grouped_job):
    """``bad``, ``col``, ``adj`` on every ``polish wave`` (PR 33): the
    collapse stage runs iff a tet is under the threshold at the wave's
    entry, and the two skip counters are the waves' own sums."""
    for which in ("cold", "warm"):
        job = grouped_job[which]
        recs, _ = _tree(job["records"])
        waves = [r for r in recs if r["name"] == "polish wave"]
        assert all({"bad", "col", "adj"} <= set(r) for r in waves)
        assert all(r["col"] == int(r["bad"] > 0) for r in waves)
        assert all(r["collapse"] == 0 for r in waves if not r["col"])
        assert job["counters"]["tail.collapse_skipped"] == sum(
            1 - r["col"] for r in waves)
        assert job["counters"]["tail.exit_adj_skipped"] == sum(
            1 - r["adj"] for r in waves)


def test_polish_waves_say_how_their_tables_were_made(grouped_job):
    """``tab``, ``inc`` on every ``polish wave`` (PR 38): the tables the
    wave derived and those of them taken off the retained sort; a job's
    first edge table and first adjacency have nothing to merge into, and
    the two counters are the waves' own sums."""
    for which in ("cold", "warm"):
        job = grouped_job[which]
        recs, _ = _tree(job["records"])
        waves = [r for r in recs if r["name"] == "polish wave"]
        assert all({"tab", "inc"} <= set(r) for r in waves)
        assert all(r["tab"] == r["col"] + 3 + r["adj"] for r in waves)
        assert waves[0]["inc"] == waves[0]["tab"] - 2
        assert all(r["inc"] == r["tab"] for r in waves[1:])
        assert job["counters"]["tail.tables"] == sum(
            r["tab"] for r in waves)
        assert job["counters"]["tail.tables_merged"] == sum(
            r["inc"] for r in waves)


def test_fem_rounds_say_how_their_tables_were_made(grouped_job):
    """``tab``, ``inc`` on every ``fem round`` of a grouped job (PR 44):
    the round's edge table and adjacency, and those of them taken off
    the sorts the merged polish handed on: all, every round's first too.
    ``tail.fem_tables`` / ``tail.fem_tables_merged`` are the rounds' own
    sums, once a job."""
    for which in ("cold", "warm"):
        job = grouped_job[which]
        recs, _ = _tree(job["records"])
        rounds = [r for r in recs if r["name"] == "fem round"]
        assert rounds and all({"tab", "inc"} <= set(r) for r in rounds)
        assert all((r["tab"], r["inc"]) == (2, 2) for r in rounds)
        assert {"tail.fem_tables", "tail.fem_tables_merged"} <= \
            set(job["counters"])
        assert job["counters"]["tail.fem_tables"] == sum(
            r["tab"] for r in rounds)
        assert job["counters"]["tail.fem_tables_merged"] == sum(
            r["inc"] for r in rounds)


def test_tail_rows_are_counted_once_a_job(grouped_job):
    """``tail.rows_live`` / ``tail.rows_cap``: the mesh the merged tail
    is about to run on, which is the last merge's, at the capacity
    ``merge_shards`` chose for it (1.5x its content)."""
    for which in ("cold", "warm"):
        job = grouped_job[which]
        recs, _ = _tree(job["records"])
        merges = [r for r in recs if r["name"] == "grp merge"]
        assert len(merges) == 2
        assert all(r["ne"] <= r["capT"] <= (3 * r["ne"]) // 2 + 64
                   and r["capP"] > 0 for r in merges)
        assert job["counters"]["tail.rows_live"] == merges[-1]["ne"]
        assert job["counters"]["tail.rows_cap"] == merges[-1]["capT"]


@pytest.mark.parametrize("parent", ["run", "adaptation",
                                    "bad-element polish"])
def test_children_cover_their_parent(grouped_job, parent):
    """No host time hides between the spans: the children of a parent
    lie inside it and cover at least 95 % of it."""
    recs, _ = _tree(grouped_job["cold"]["records"])
    top = [r for r in recs if r["name"] == parent][0]
    lo, hi = _interval(top)
    kids = sorted(_interval(r) for r in recs
                  if r.get("parent") == top["id"] and "t0" in r)
    covered, end = 0, lo
    for s, e in kids:
        assert lo <= s <= e <= hi
        covered += max(0, e - max(s, end))
        end = max(end, e)
    assert covered >= 0.95 * (hi - lo), covered / (hi - lo)


def test_ring_drops_nothing_over_a_job(grouped_job):
    for which in ("cold", "warm"):
        s = grouped_job[which]["summary"]
        assert s["dropped"] == 0 and s["events"] == s["ring"] > 0


def test_unarmed_checkpoint_emits_no_span(grouped_job):
    cold, _ = _tree(grouped_job["cold"]["records"])
    warm, _ = _tree(grouped_job["warm"]["records"])
    # armed: the stacked snapshot and the pass file of each pass
    assert sum(r["name"] == "grp checkpoint" for r in cold) == 4
    assert not any(r["name"] == "grp checkpoint" for r in warm)
    # a warm job compiles nothing but what recompiles every job
    compiles = [r for r in grouped_job["warm"]["records"]
                if r.get("name") == "compile"]
    assert all("parent" in r for r in compiles)
    assert grouped_job["warm"]["counters"].get(
        "compile.backend_n", 0) == len(compiles)


def test_operator_capture_holds_the_program_spans(grouped_job):
    """PARMMG_PROFILE_DIR: one capture over one whole run, and the spans
    are on the profiler's own timeline."""
    import glob
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(grouped_job["profile_dir"], "plugins",
                                   "profile", "*", "*.xplane.pb"))
    assert len(found) == 1
    names = set()
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            names |= {ev.name for ev in line.events}
    assert {"run", "analysis", "adaptation", "grp split", "grp block",
            "grp merge", "bad-element polish", "polish wave"} <= names
    kinds = [r["name"] for r in grouped_job["warm"]["records"]
             if r.get("name", "").startswith("profile_")]
    assert kinds == ["profile_start", "profile_stop"]


def _device_phases(records):
    return [r for r in records if r.get("name") == "device_phases"]


def test_a_job_without_the_capture_asks_for_no_map_and_no_digest(
        grouped_job):
    """``scope_map`` and ``digest`` cost nothing unless called, and no
    job calls them unless ``PARMMG_PROFILE_DIR`` armed the capture."""
    assert grouped_job["cold"]["asked"] == {"scope_map": 0, "digest": 0}
    assert _device_phases(grouped_job["cold"]["records"]) == []
    assert grouped_job["warm"]["asked"]["digest"] == 1
    assert grouped_job["warm"]["asked"]["scope_map"] >= 1
    # the maps came out of jax's own caches: no compile was theirs
    stop = next(i for i, r in enumerate(grouped_job["warm"]["records"])
                if r.get("name") == "profile_stop")
    assert not [r for r in grouped_job["warm"]["records"][stop:]
                if r.get("name") == "compile"]


def test_the_capture_is_digested_into_one_device_phases_event(grouped_job):
    recs, by_id = _tree(grouped_job["warm"]["records"])
    events = _device_phases(grouped_job["warm"]["records"])
    assert len(events) == 1
    ev = events[0]
    assert by_id[ev["parent"]]["name"] == "run"
    assert ev["phases"] and all(p.startswith("cyc.") for p in ev["phases"])
    assert {"cyc.table", "cyc.split", "cyc.collapse", "cyc.smooth",
            "cyc.adjacency"} <= set(ev["phases"])
    assert sum(ev["phases"].values()) + ev["unscoped"] == \
        pytest.approx(ev["block_s"])
    assert ev["block_s"] > 0 and ev["busy_s"] > 0
    # a table's seconds are part of the phase they ran in
    assert set(ev["tables"]) == {"tab.edges", "tab.adjacency"}
    for rows in ev["tables"].values():
        assert all(0 < s <= ev["phases"][p] for p, s in rows.items())
    # the merged polish and the fem rounds run on the same backend
    # here: their stages too, and a row a wave, a row a round
    for field, prefix, span in (("polish", "pol.", "polish wave"),
                                ("fem", "fem.", "fem round")):
        part = ev[field]
        assert part["phases"] and all(p.startswith(prefix)
                                      for p in part["phases"])
        spans = [r for r in recs if r["name"] == span]
        assert len(part["rows"]) == len(spans) > 0
        assert [r["wave"] for r in part["rows"]] == \
            [r["wave"] for r in spans]
        assert sum(r["device_s"] for r in part["rows"]) + \
            part["outside"] == pytest.approx(part["total"])
    assert set(ev["fem"]["phases"]) == {"fem.split", "fem.bdytags",
                                        "fem.adjacency"}
    # with the polish's state carried the round's tables are the merged
    # polish's own derivations, still under the round's stages
    assert set(ev["fem"]["tables"]["tab.edges"]) == {"fem.split"}
    assert set(ev["fem"]["tables"]["tab.adjacency"]) == {"fem.adjacency"}
    assert ev["digest_s"] > 0


def test_the_digest_has_a_row_a_block_and_the_rows_add_up(grouped_job):
    recs, _ = _tree(grouped_job["warm"]["records"])
    blocks = [r for r in recs if r["name"] == "grp block"]
    ev = _device_phases(grouped_job["warm"]["records"])[0]
    assert len(ev["blocks"]) == len(blocks) > 0
    for row, span in zip(ev["blocks"], blocks):
        assert (row["pass"], row["block"]) == (span["pass"], span["block"])
        assert row["prog"] == span["prog"]
        # (no bound by the span's seconds here: the CPU backend runs a
        # program's ops on several threads, and their seconds add up)
        assert row["device_s"] > 0
    assert sum(r["device_s"] for r in ev["blocks"]) == \
        pytest.approx(ev["block_s"])
    # a swap-inclusive block has the swap phases, a plain one has not
    with_swap = [r for r in ev["blocks"] if "cyc.swap_edges" in r["phases"]]
    assert 0 < len(with_swap) < len(blocks)


def test_a_block_span_says_which_program_it_ran(grouped_job):
    """``prog``: the index of the lowered key a dispatch ran; the second
    pass of this job runs groups of another capacity, so another one.
    A polish wave and a fem round say so too."""
    for which in ("cold", "warm"):
        recs, _ = _tree(grouped_job[which]["records"])
        progs = [r["prog"] for r in recs if r["name"] == "grp block"]
        assert progs and all(isinstance(p, int) for p in progs)
        # (an index among ALL the block programs this process lowered:
        # 0 and 1 in a process of its own)
        assert progs == sorted(progs) and progs[-1] == progs[0] + 1
        for span in ("polish wave", "fem round"):
            progs = [r["prog"] for r in recs if r["name"] == span]
            assert progs and all(isinstance(p, int) for p in progs)


def test_a_kept_capture_prints_the_same_table(grouped_job, capsys):
    """``python3 -m parmmg_tpu.obs.devtime <dir>``: the program left its
    maps beside the capture."""
    from parmmg_tpu.obs import devtime
    assert devtime.main([grouped_job["profile_dir"]]) == 0
    out = capsys.readouterr().out
    assert "device seconds by phase, jit_run" in out
    assert "cyc.adjacency" in out and "tab.edges" in out
    assert devtime.main([grouped_job["profile_dir"] + "/nothing"]) == 1
