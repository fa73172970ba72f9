#!/usr/bin/env python3
"""Record the small capture that test_trace_reduce.py reads (on a TPU).

    python benchmarks/tests/record_trace.py <out.xplane.pb>

Two "cycle blocks" of a toy program — a sort, a scatter-add, a gather and
two of the program's Pallas kernels under ``grp_cycle0`` — inside one
``bench.job`` annotation, with a host sleep between them so the device
has a gap to name.  Prints what it did; the test's expected numbers were
read from this file with ``python benchmarks/trace_reduce.py <file>``.
"""
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from parmmg_tpu.ops import pallas_kernels as pk   # noqa: E402

N = 6 * 4096


@jax.jit
def block(p0, p1, h0, h1, idx):
    with jax.named_scope("grp_cycle0"):
        ln = pk.edge_length_iso_pallas(p0, p1, h0, h1)
        score, cnt = pk.score_count_pallas(ln > 1.0, ln)
        order = jnp.argsort(score)
        acc = jnp.zeros(N // 6, jnp.float32).at[idx].add(ln)
        return acc[idx[order]] + cnt


def main() -> int:
    out = sys.argv[1]
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    p0, p1 = (jax.random.uniform(k, (N, 3)) for k in key[:2])
    h0, h1 = (0.1 + jax.random.uniform(k, (N,)) for k in key[2:4])
    idx = jax.random.randint(key[4], (N,), 0, N // 6)
    block(p0, p1, h0, h1, idx).block_until_ready()      # compile outside
    tmp = out + ".dir"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.job"):
        with jax.profiler.TraceAnnotation("bench.run"):
            block(p0, p1, h0, h1, idx).block_until_ready()
            with jax.profiler.TraceAnnotation("host_nap"):
                time.sleep(0.05)
            block(p0, p1, h0, h1, idx).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copyfile(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out}: {os.path.getsize(out)} bytes, 2 blocks of N={N}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
