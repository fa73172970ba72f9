#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once, in one process.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``): imports, the input from ``--seed``, the persistent
compile cache, ONE whole job that loads or compiles every program.  Then
the window: whole jobs back to back for ``--seconds`` (traffic.py).  Then,
outside every timed interval, the plain reference (checker.py) on the
output of the warm-up job and of every job of the window.  The last line
of stdout is the result; everything else goes to stderr.

Fails, printing no result, when jax finds no TPU.  To rehearse on the CPU
the caller sets JAX_PLATFORMS=cpu AND passes --rehearse: the line then
says ``platform: cpu`` and carries no device metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # as near to process start as we get

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_BYTES = 8 << 30       # room for the 24 cells the contract allows


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def judge_job(job: dict, config: dict) -> dict:
    """The plain reference on one job's output; prints each number
    compared beside its limit."""
    import checker
    numbers = checker.measure(job["vert"], job["tet"], job["met"],
                              config["domain"])
    numbers["degraded"] = int(job["rc"] != 0) + sum(
        int(v > 0) for k, v in job["counters"].items()
        if k.startswith("resilience."))
    rows = checker.judge(numbers, config["guarantees"])
    job["numbers"] = numbers
    job["ok"] = all(r["ok"] for r in rows)
    say(f"  check {job['label']}: " + "; ".join(
        f"{r['name']} {r['value']!r} (limit {r['limit']!r})"
        + ("" if r["ok"] else " FAILED") for r in rows))
    return job


def measure_cell(bench: dict, cell: dict, config: dict, traffic: dict,
                 args, run_job, device: dict, compiles: dict,
                 tracer=None, peaks=None) -> dict:
    """Everything of a run after the look for a chip: the input, the
    warm-up job, the window, the checks, the result.  ``run_job(inp,
    annotate=None)`` runs one adaptation; ``tracer`` (None in a CPU
    rehearsal) wraps the traced job in a profiler capture and reduces
    it; ``compiles`` is the live count of backend compiles."""
    import traffic as trafficmod
    from byname import load
    inp = trafficmod.job_input(config, traffic, args.seed, run_job)
    warm = run_job(inp)
    warm["label"] = "warm-up"
    n_setup, s_setup = compiles["n"], compiles["s"]
    say(f"set-up: input {len(inp['vert'])} vertices / {len(inp['tet'])} tets;"
        f" warm-up job {warm['seconds']:.2f} s; {n_setup} backend compiles, "
        f"{s_setup:.1f} s; {compiles['cache_hits']} programs from "
        "the persistent cache")

    def run_one(i):
        if args.trace and i == 0 and tracer is not None:
            res = tracer.run(lambda mark: run_job(inp, mark))
            res["traced"] = True
            return res
        return run_job(inp)

    setup_s = time.perf_counter() - T_START
    jobs = trafficmod.closed_loop(run_one, args.seconds, time.perf_counter)
    window_compiles = compiles["n"] - n_setup
    # ---- the window is closed; nothing below is timed -------------------
    for i, j in enumerate(jobs):
        j["label"] = f"job {i}"
    checked = [judge_job(j, config) for j in [warm] + jobs]
    say(f"window: {len(jobs)} job(s) in {jobs[-1]['end_s']:.2f} s of "
        f"{args.seconds:g}; {window_compiles} backend compiles inside "
        f"{compiles['names'][n_setup:]}")
    result = {"correct": all(j["ok"] for j in checked),
              "attempted": len(jobs),
              "failed": sum(not j["ok"] for j in jobs),
              "metrics": {}, "device": device}
    run = {"setup_s": setup_s, "jobs": jobs, "chips": cell["chips"],
           "trace": None, "peaks": peaks, "window_compiles": window_compiles}
    if args.trace and tracer is not None:
        run["trace"] = tracer.reduce(next(j for j in jobs if j.get("traced")))
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    kind = "layer_metrics" if args.trace else "end_to_end"
    for m in bench["per_layer" if args.trace else "end_to_end"]:
        value = load(kind, m["name"]).read(run)
        if value is not None:       # a reader that found nothing to read
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    return result


def enable_cache() -> str:
    """The program's one cache rule (JAX_COMPILATION_CACHE_DIR if set,
    else <checkout>/.jax_cache; off on the pinned CPU backend), with room
    for what the cells compile: a job compiles TWO block programs of
    about 115 MB each (PERF.md, PR 27), and under a cap that holds one
    (the chip machines come with 192 MiB) each evicts the other and no
    run ever starts warm."""
    import jax
    from parmmg_tpu.utils.compilecache import enable_persistent_cache
    cache_dir = enable_persistent_cache()
    if 0 <= jax.config.jax_compilation_cache_max_size < CACHE_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)
    return cache_dir


def job_runner(config: dict):
    """``run_job(inp, annotate=None)`` for a configuration.  The program
    prints its reports on stdout; ours is one line."""
    import job as jobmod

    def run_job(inp, annotate=None):
        with contextlib.redirect_stdout(sys.stderr):
            return jobmod.run_job(inp, config["options"], annotate)
    return run_job


class Tracer:
    """A profiler capture of ONE whole job, ours (the program's own,
    PARMMG_PROFILE_DIR, covers the grouped passes only), and its
    reduction.  The capture is deleted once reduced."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir

    def run(self, job):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host TraceMes, no Python frames
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.job"):
                return job(jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()

    def reduce(self, traced_job: dict) -> dict:
        import trace_reduce
        try:
            return trace_reduce.reduce_dir(self.dir, traced_job)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="with JAX_PLATFORMS=cpu: run on the CPU, report "
                         "no device metric")
    args = ap.parse_args()

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    config = load_json(ROOT, find(bench["configs"], cell["config"],
                                  "configuration")["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    sys.path[:0] = [HERE, ROOT]

    import jax
    dev = jax.devices()[0]
    rehearsal = (args.rehearse and dev.platform == "cpu"
                 and os.environ.get("JAX_PLATFORMS", "") == "cpu")
    if not rehearsal and (dev.platform != "tpu"
                          or len(jax.devices()) < cell["chips"]):
        say(f"run.py: the cell needs {cell['chips']} TPU chip(s); jax found "
            f"{len(jax.devices())} x {dev.platform!r}. Nothing was run.")
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    peaks = None
    if not rehearsal:
        table = load_json(HERE, "peaks.json")
        if dev.device_kind not in table:
            say(f"run.py: no peaks for device kind {dev.device_kind!r} in "
                "benchmarks/peaks.json")
            return 2
        peaks = table[dev.device_kind]

    cache_dir = enable_cache()
    compiles = {"n": 0, "s": 0.0, "names": [], "cache_hits": 0}

    def on_duration(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles["n"] += 1
            compiles["s"] += duration
            compiles["names"].append(str(kw.get("fun_name", "?")))

    def on_event(event, **_kw):
        if event == CACHE_HIT_EVENT:
            compiles["cache_hits"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {args.seed}; device {device}; cache "
        f"{cache_dir or 'off'}")
    tracer = None if rehearsal else Tracer(
        os.path.join(ROOT, ".bench_out", "trace",
                     f"{cell['name']}-{args.seed}"))
    result = measure_cell(bench, cell, config, traffic, args,
                          job_runner(config), device, compiles, tracer,
                          peaks)
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
