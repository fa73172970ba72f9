"""Run the serving pool daemon (parmmg_tpu/serve/daemon.py).

The persistent-pool service of ROADMAP item 3a: one long-lived process
owns the warm compiled group programs AND the persistent compile cache
for its lifetime, fronting ``ServeDriver.submit/poll/fetch`` over a
stdlib HTTP/JSON RPC surface so clients churn while slots stay hot:

    python scripts/serve_daemon.py --port 8077 --cycles 6 &
    python - <<'EOF'
    from parmmg_tpu.serve.client import ServeClient
    cl = ServeClient(port=8077)
    tid = cl.submit(path="/abs/path/job.mesh", tenant="job-1")
    cl.wait(tid); print(cl.poll(tid))
    EOF

Endpoints: POST /submit (429 under backpressure), GET /poll /fetch
/healthz /metrics /report, POST /pause /resume /step /shutdown.
Foregrounds until SIGINT or a /shutdown RPC.

Knobs ride the PARMMG_SERVE_* env surface (see api/knobs.py): PORT,
SLOTS, CHUNK, MAX_QUEUE, STREAM, AUTOSCALE, MAX_SLOTS, TARGET_P99_S,
TIMEOUT_S, MAX_INFLIGHT, MAX_CAPP/MAX_CAPT, SLO_QMIN.  The cache knobs
follow the CLI policy: ``--cache-dir`` (or a pre-set
JAX_COMPILATION_CACHE_DIR) opts the pinned-CPU daemon into the
persistent cache; accelerator backends get it by default.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# same defensive backend sequence as scripts/serve_run.py: pin CPU
# unless the operator asked for an accelerator via SERVE_DEVICE
if os.environ.get("SERVE_DEVICE", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=None,
                    help="bind port (default PARMMG_SERVE_PORT, 8077; "
                         "0 = ephemeral)")
    ap.add_argument("--cycles", type=int,
                    default=int(os.environ.get("SERVE_CYCLES", "6")))
    ap.add_argument("--out", default=None,
                    help="optional merge-free checkpoint directory")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compile cache directory the "
                         "daemon owns for its lifetime")
    ap.add_argument("--paused", action="store_true",
                    help="start with the serving loop paused")
    ap.add_argument("-v", "--verbose", action="count", default=0)
    args = ap.parse_args()

    # the daemon owns the persistent compile cache (the CLI policy,
    # compilecache.py); a missing accelerator without the
    # JAX_PLATFORMS=cpu pin is an error, not a quieter daemon
    from parmmg_tpu.utils.compilecache import (backend_or_fail,
                                               enable_persistent_cache)
    enable_persistent_cache(args.cache_dir)
    backend_or_fail()

    from parmmg_tpu.obs import trace as otrace
    from parmmg_tpu.serve.daemon import PoolDaemon

    if args.out:
        os.makedirs(args.out, exist_ok=True)
    daemon = PoolDaemon(host=args.host, port=args.port,
                        start_paused=args.paused, out_dir=args.out,
                        cycles=args.cycles, verbose=args.verbose)
    daemon.start()
    otrace.log(0, f"serve daemon: pid {os.getpid()} on "
                  f"http://{daemon.host}:{daemon.port} "
                  f"(backend {jax.default_backend()})", err=True)
    try:
        while daemon.alive():
            time.sleep(0.5)
    except KeyboardInterrupt:
        otrace.log(0, "serve daemon: SIGINT, shutting down", err=True)
        daemon.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
