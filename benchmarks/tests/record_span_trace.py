#!/usr/bin/env python3
"""Record the small capture that holds PROGRAM spans (on a TPU).

    python benchmarks/tests/record_span_trace.py <out.xplane.pb>

record_trace.py's toy block, run the way the program runs a job since
PR 28: under ``parmmg_tpu.obs.trace.span`` and a ``Timers`` scope, so the
capture carries the program's own annotations beside the benchmark's
``bench.*`` ones, with host naps for the device to idle in.  Beside the
capture it writes ``<out>.spans.json``: the ring's spans as job.py hands
them to the reducer, and the epoch second the job began at.
"""
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import jax                                  # noqa: E402

from record_trace import N, block           # noqa: E402  (adds ROOT)

from parmmg_tpu.obs import trace as otrace  # noqa: E402
from parmmg_tpu.utils.timers import Timers  # noqa: E402


def main() -> int:
    out = sys.argv[1]
    if jax.devices()[0].platform != "tpu":
        print("record_span_trace.py: needs a TPU", file=sys.stderr)
        return 2
    # off the persistent cache: the chip machines cap it at 192 MiB, and
    # one entry written under that cap evicts the cells' block programs
    jax.config.update("jax_enable_compilation_cache", False)
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    p0, p1 = (jax.random.uniform(k, (N, 3)) for k in key[:2])
    h0, h1 = (0.1 + jax.random.uniform(k, (N,)) for k in key[2:4])
    idx = jax.random.randint(key[4], (N,), 0, N // 6)
    args = (p0, p1, h0, h1, idx)
    block(*args).block_until_ready()        # compile outside
    tmp = out + ".dir"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    otrace.new_run()
    otrace.TRACER.reset()
    tim = Timers()
    with jax.profiler.TraceAnnotation("bench.job"):
        t_epoch = time.time()
        with jax.profiler.TraceAnnotation("bench.run"), otrace.span("run"):
            with tim("adaptation"):
                with otrace.span("grp block", block=0):
                    block(*args).block_until_ready()
                with otrace.span("grp merge"):
                    time.sleep(0.05)
                with otrace.span("grp block", block=1):
                    block(*args).block_until_ready()
            with tim("bad-element polish"):
                for wave in range(2):
                    with otrace.span("polish wave", wave=wave):
                        time.sleep(0.02)
    jax.profiler.stop_trace()
    spans = [(r["name"], r["ts"] - float(r["dur"]), r["ts"])
             for r in otrace.TRACER.ring if r.get("kind") == "span"]
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copyfile(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    with open(out + ".spans.json", "w") as f:
        json.dump({"t_epoch": t_epoch, "spans": spans}, f)
    print(f"wrote {out}: {os.path.getsize(out)} bytes, {len(spans)} spans")
    return 0


if __name__ == "__main__":
    sys.exit(main())
