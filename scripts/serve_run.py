"""File-based front-end for the serving subsystem (parmmg_tpu/serve).

Submit a batch of tenant mesh files to one warm pool and write each
tenant's adapted mesh back out as a merge-free distributed checkpoint:

    python scripts/serve_run.py --out OUTDIR a.mesh b.mesh c.vtu ...

Each input may carry a sidecar metric ``<stem>.sol`` (auto-detected;
VTK inputs may embed a "metric"/"sol" point field instead); without
one the -optim default metric is synthesized.  Prints ONE JSON report:
per-tenant state / latency / qmin / qmean / output files plus the pool
aggregates (occupancy, dispatches, chunk recommendation).

Knobs ride the PARMMG_SERVE_* env surface (see serve/driver.py):
SLOTS, CHUNK, CYCLES (SERVE_CYCLES here), MAX_INFLIGHT, TIMEOUT_S,
MAX_CAPP/MAX_CAPT.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# the serving orchestrator is host-side: CPU unless SERVE_DEVICE says
# otherwise
if os.environ.get("SERVE_DEVICE", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("meshes", nargs="+", help=".mesh/.meshb/.vtu inputs")
    ap.add_argument("--out", default="serve_out",
                    help="output directory for per-tenant checkpoints")
    ap.add_argument("--cycles", type=int,
                    default=int(os.environ.get("SERVE_CYCLES", "6")))
    ap.add_argument("-v", "--verbose", action="count", default=0)
    args = ap.parse_args()

    from parmmg_tpu.serve.driver import ServeDriver

    os.makedirs(args.out, exist_ok=True)
    drv = ServeDriver(out_dir=args.out, cycles=args.cycles,
                      verbose=args.verbose)
    for p in args.meshes:
        stem = os.path.splitext(p)[0]
        sol = stem + ".sol"
        drv.submit(path=p, sol=sol if os.path.exists(sol) else None,
                   tenant=os.path.basename(stem))
    rep = drv.run()
    rep.pop("occupancy_traj", None)
    print(json.dumps(rep, default=str))
    return 0 if rep["failed"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
