#!/usr/bin/env python3
"""A cell's jobs on many seeds in ONE process (one set-up), sound or as
the control: what the checker's limits are set from.

    python3 benchmarks/seeds.py --workload <name> --seeds 1,2,3 [--control one-pass|bfloat16] [--rehearse]

For every seed: one job at the cell's own size through the timed path's
entry (job.run_job) and the checker's numbers for its output, each beside
its limit.  ``--control`` (control.py) makes them jobs of the program
with a guarantee broken (``one-pass``: every seed has to come out NOT
correct) or with its length and quality arithmetic in bfloat16 (a
reading: PERF.md section 2 says what it showed).  One JSON line a seed on
stdout.  Not part of a run: the limits it produced are in the
configurations' files and in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", choices=("one-pass", "bfloat16"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path[:0] = [HERE, ROOT]
    import run as harness
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find(bench["workloads"], args.workload, "workload")
    config = harness.load_json(ROOT, harness.find(
        bench["configs"], cell["config"], "configuration")["file"])
    traffic = harness.load_json(HERE, "traffic", cell["traffic"] + ".json")

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not (
            args.rehearse and os.environ.get("JAX_PLATFORMS") == "cpu"):
        print("seeds.py: no TPU; nothing was run", file=sys.stderr)
        return 2
    harness.enable_cache()
    if args.control:
        import control
        config = control.apply(args.control, config)
    import traffic as trafficmod
    run_job = harness.job_runner(config)

    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_job(trafficmod.job_input(config, traffic, seed, run_job))
        out["label"] = f"seed {seed}" + (
            f" control {args.control}" if args.control else "")
        harness.judge_job(out, config)
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "control": args.control, "platform": dev.platform,
            "job_s": out["seconds"], "ok": out["ok"],
            "numbers": out["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
