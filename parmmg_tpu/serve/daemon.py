"""Pool daemon: the persistent serving process (ROADMAP item 3a).

``SlotPool``/``ServeDriver`` made multi-tenant serving a LIBRARY: the
warm compiled ``_group_block`` programs — and with them the whole
zero-compile serving story — died with the one submitting process.
This module makes it a SERVICE: a daemon owns one ``ServeDriver`` (and
thereby the warm compiled programs, the compile ledger, and the
persistent compile cache configured at startup) for its lifetime, and
fronts ``submit/poll/fetch`` with a thin stdlib HTTP/JSON RPC layer so
clients churn while slots stay hot.

Transport (stdlib only, localhost-class): JSON bodies; mesh arrays ride
base64 npz (bit-exact in both directions).  Endpoints:

    POST /submit    {tenant?, npz_b64?, path?, sol?} -> {tid}
                    (HTTP 429 {error, deferred:true} under admission
                    backpressure — retry later)
    GET  /poll?tid= -> request state machine position
    GET  /fetch?tid=-> {npz_b64}: merged mesh fields + met (409 until
                    the request is done)
    GET  /healthz   -> liveness + loop counters
    GET  /metrics   -> Prometheus text exposition (obs registry)
    GET  /report    -> the full ServeDriver report
    POST /pause /resume /step /shutdown  (ops + deterministic tests;
                    /step runs exactly one serving-loop iteration)

Threads: one HTTP server (per-request handler threads) + one serving
loop; a single re-entrant lock serializes driver access, so RPC
handlers observe consistent state between steps.

Failure semantics: the RPC dispatch is a named faultpoint
(``serve.daemon_rpc``, armed via PARMMG_FAULT) — an injected or real
fault while handling a tenant's request kills THAT request mid-flight:
the tenant is quarantined (``ServeDriver.quarantine``: retired FAILED,
slot scrubbed + recycled) while cohort-mates keep their bit-identical
results and the daemon keeps serving (gated by run_tests.sh --chaos).
The serving loop composes with the PR 9 ladder unchanged (slot
retries, slot-fault quarantine).

Hang semantics: each serving-loop step runs under an optional
``PARMMG_DEADLINE_SERVE_S`` watchdog (resilience/watchdog.py).  The
first-use grace (``PARMMG_DEADLINE_GRACE_S``) distinguishes the
legitimate cold-compile first step from a wedged loop; on expiry the
daemon flips ``/healthz`` to not-ok with ``wedged: true`` and waits
the stuck step out instead of piling new steps behind the held lock.
"""
from __future__ import annotations

import base64
import io
import json
import threading
from http.server import BaseHTTPRequestHandler

import numpy as np

from .driver import ServeDriver
from .pool import _env_int

__all__ = ["PoolDaemon", "decode_npz", "encode_npz", "mesh_arrays"]


# ---------------------------------------------------------------------------
# bit-exact array transport (base64 npz)
# ---------------------------------------------------------------------------
def mesh_arrays(mesh, met=None) -> dict:
    """Merged (mesh, met) -> {field: np.ndarray} payload.  Accepts a
    core Mesh (MESH_FIELDS) or a plain dict of arrays (the host-only
    stub pools of the tier-1 tests)."""
    if isinstance(mesh, dict):
        out = {k: np.asarray(v) for k, v in mesh.items()}
    else:
        from ..core.mesh import MESH_FIELDS
        out = {f: np.asarray(getattr(mesh, f)) for f in MESH_FIELDS}
    if met is not None:
        out["met"] = np.asarray(met)
    return out


def encode_npz(arrays: dict) -> str:
    buf = io.BytesIO()
    np.savez_compressed(buf, **arrays)
    return base64.b64encode(buf.getvalue()).decode("ascii")


def decode_npz(b64: str) -> dict:
    raw = base64.b64decode(b64.encode("ascii"))
    with np.load(io.BytesIO(raw), allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


# ---------------------------------------------------------------------------
# the daemon
# ---------------------------------------------------------------------------
class PoolDaemon:
    """Persistent pool service: HTTP front-end + serving-loop thread
    around one :class:`ServeDriver`.

    ``port`` defaults to PARMMG_SERVE_PORT (8077); ``port=0`` binds an
    ephemeral port (tests/gates), readable from :attr:`port` after
    :meth:`start`.  ``start_paused`` starts with the loop idle (ops can
    /pause-/resume-/step- the loop deterministically)."""

    def __init__(self, driver: ServeDriver | None = None,
                 host: str = "127.0.0.1", port: int | None = None,
                 idle_sleep_s: float = 0.02, start_paused: bool = False,
                 **driver_kwargs):
        self.driver = driver if driver is not None \
            else ServeDriver(**driver_kwargs)
        self.host = host
        self.port = port if port is not None \
            else _env_int("PARMMG_SERVE_PORT", 8077)
        self.idle_sleep_s = float(idle_sleep_s)
        self.paused = bool(start_paused)
        self._wedged = False
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._httpd = None
        self._threads: list[threading.Thread] = []

    # ---- lifecycle --------------------------------------------------------
    def start(self) -> "PoolDaemon":
        from http.server import ThreadingHTTPServer

        from ..obs import trace as otrace
        if self._httpd is not None:
            raise RuntimeError("daemon already started")
        self._httpd = ThreadingHTTPServer((self.host, self.port),
                                          _Handler)
        self._httpd.daemon_threads = True
        self._httpd.pool_daemon = self
        self.port = int(self._httpd.server_address[1])
        self._threads = [
            threading.Thread(target=self._httpd.serve_forever,
                             name="parmmg-serve-http", daemon=True),
            threading.Thread(target=self._loop,
                             name="parmmg-serve-loop", daemon=True),
        ]
        for t in self._threads:
            t.start()
        otrace.event("serve.daemon_start", port=self.port)
        otrace.log(1, f"serve daemon: listening on "
                      f"http://{self.host}:{self.port}", err=True)
        return self

    def _loop(self) -> None:
        from ..obs import trace as otrace
        from ..obs.metrics import REGISTRY
        from ..resilience.watchdog import (WatchdogTimeout,
                                           deadline_knob,
                                           run_with_deadline)

        def step():
            # the lock is taken INSIDE the guarded fn so a wedged step
            # is observable: the watchdog thread owns the RLock for the
            # step's whole (possibly unbounded) duration, /healthz
            # stays lock-free by design
            with self._lock:
                # lint: ok(R9) — the hold IS the design: serving and
                # RPCs serialize on the RLock, and this exact hold is
                # what run_with_deadline(PARMMG_DEADLINE_SERVE_S)
                # bounds; the one subprocess leg inside (the
                # native-ext build) is one-time and memoized
                return self.driver.service_once()

        while not self._stop.is_set():
            if self.paused:
                self._stop.wait(self.idle_sleep_s)
                continue
            # re-read each iteration: ops can arm/disarm the step
            # deadline on a live daemon.  run_with_deadline's first-use
            # grace (PARMMG_DEADLINE_GRACE_S) absorbs the legitimate
            # cold-compile first step; after that, a step exceeding the
            # budget is a WEDGED loop, not a slow one.
            dl = deadline_knob("PARMMG_DEADLINE_SERVE_S")
            try:
                st = run_with_deadline(step, dl, "serve.slot_step")
            except WatchdogTimeout as e:
                # the abandoned step thread still holds the RLock:
                # spawning more steps would just pile up behind it.
                # Mark the daemon wedged (healthz flips not-ok so a
                # supervisor can restart it) and wait the thread out —
                # if it ever finishes, serving resumes.
                REGISTRY.counter("serve.step_timeouts").inc()
                otrace.event("serve.step_timeout",
                             seconds=float(e.seconds))
                otrace.log(0, f"serve daemon: serving step exceeded "
                              f"{e.seconds:g}s deadline — wedged "
                              "(healthz not-ok) until it returns",
                           err=True)
                # lint: ok(R9) — GIL-atomic bool store: only this loop
                # thread ever writes _wedged; /healthz reads it
                # lock-free BY DESIGN (a liveness probe must answer
                # while the abandoned step still owns the RLock —
                # taking the lock here would recreate the wedge)
                self._wedged = True
                th = getattr(e, "thread", None)
                while th is not None and th.is_alive() \
                        and not self._stop.is_set():
                    self._stop.wait(max(self.idle_sleep_s, 0.1))
                # lint: ok(R9) — same GIL-atomic probe flag as above
                self._wedged = False
                continue
            except Exception as e:
                # the loop is the service: an escaped iteration error
                # (a degenerate merge, an actuation failure) must not
                # silently kill serving while /healthz stays green —
                # account it, back off, keep looping (per-tenant fault
                # containment already happened below this level)
                REGISTRY.counter("serve.loop_errors").inc()
                otrace.event("serve.loop_error", detail=repr(e)[:300])
                otrace.log(0, f"serve daemon: serving-loop iteration "
                              f"failed ({e!r}); continuing", err=True)
                self._stop.wait(max(self.idle_sleep_s, 0.1))
                continue
            if st != "active":
                # idle, or stalled on capacity: a daemon WAITS (new
                # submissions / autoscale / timeouts resolve it) rather
                # than mass-rejecting like the batch run() loop
                self._stop.wait(self.idle_sleep_s)

    def shutdown(self) -> None:
        from ..obs import trace as otrace
        self._stop.set()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
        for t in self._threads:
            if t is not threading.current_thread():
                t.join(timeout=10)
        otrace.event("serve.daemon_stop", port=self.port)
        otrace.log(1, "serve daemon: stopped", err=True)

    def alive(self) -> bool:
        return any(t.is_alive() for t in self._threads)

    def __enter__(self) -> "PoolDaemon":
        return self if self._httpd is not None else self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # ---- RPC dispatch -----------------------------------------------------
    def handle_rpc(self, method: str, op: str, qs: dict, payload: dict):
        """One RPC -> (status, body, content_type).  The dispatch runs
        behind the ``serve.daemon_rpc`` faultpoint: a fault here kills
        THIS request — its tenant is quarantined, the daemon and every
        other tenant keep going."""
        from ..obs import trace as otrace
        from ..obs.metrics import REGISTRY
        from ..resilience.faults import faultpoint
        tid = payload.get("tenant") or (qs.get("tid") or [None])[0]
        otrace.log(2, f"serve daemon: {method} /{op}"
                      + (f" tid={tid}" if tid else ""), err=True)
        otrace.event("serve.rpc", op=op,
                     **({"tenant": tid} if tid else {}))
        try:
            faultpoint("serve.daemon_rpc", key=tid if tid else op)
        except Exception as e:
            # the request dies mid-flight: quarantine ITS tenant, keep
            # serving everyone else (PR 9 isolation, RPC-edge form)
            q = False
            if tid:
                with self._lock:
                    # lint: ok(R9) — quarantine must retire the tenant
                    # atomically with pool state (PR 9 isolation); the
                    # only subprocess on its retire->merge path is the
                    # one-time memoized native-extension build
                    q = self.driver.quarantine(
                        tid, f"daemon rpc fault: {e!r:.200}")
            REGISTRY.counter("serve.rpc_faults").inc()
            otrace.event("serve.rpc_fault", op=op,
                         **({"tenant": tid} if tid else {}))
            otrace.log(1, f"serve daemon: RPC fault on /{op}"
                          + (f" — tenant {tid} quarantined" if q else ""),
                       err=True)
            return 500, {"error": repr(e), "quarantined": q}, None
        try:
            return self._dispatch(method, op, qs, payload, tid)
        except Exception as e:
            REGISTRY.counter("serve.rpc_errors").inc()
            otrace.log(1, f"serve daemon: /{op} failed ({e!r})",
                       err=True)
            return 500, {"error": repr(e)}, None

    def _dispatch(self, method: str, op: str, qs: dict, payload: dict,
                  tid):
        d = self.driver
        if op == "submit" and method == "POST":
            b64 = payload.get("npz_b64")
            with self._lock:
                if b64:
                    mesh, met = d.stage_payload(decode_npz(b64))
                    got, reason = d.try_submit(
                        mesh=mesh, met=met, tenant=payload.get("tenant"))
                else:
                    got, reason = d.try_submit(
                        path=payload.get("path"),
                        sol=payload.get("sol"),
                        tenant=payload.get("tenant"))
            if got is None:
                return 429, {"error": reason, "deferred": True}, None
            return 200, {"tid": got}, None
        if op == "poll":
            with self._lock:
                if tid is None or tid not in d.requests:
                    return 404, {"error": f"unknown request {tid!r}"}, \
                        None
                return 200, d.poll(tid), None
        if op == "fetch":
            with self._lock:
                if tid is None or tid not in d.requests:
                    return 404, {"error": f"unknown request {tid!r}"}, \
                        None
                try:
                    mesh, met = d.fetch(tid)
                except RuntimeError as e:
                    return 409, {"error": str(e)}, None
                arrays = mesh_arrays(mesh, met)
            return 200, {"tid": tid, "npz_b64": encode_npz(arrays)}, None
        if op == "healthz":
            # deliberately LOCK-FREE: a liveness probe must answer even
            # while the loop thread holds the driver lock through a
            # cold-compile step; the counters below are single reads of
            # host ints/lists (snapshot-racy, probe-accurate).  ok ==
            # the serving loop can make progress (paused counts: that
            # is an operator choice, not a death)
            loop_alive = bool(len(self._threads) > 1
                              and self._threads[1].is_alive())
            out = {"ok": bool((self.paused or loop_alive)
                              and not self._wedged),
                   "paused": self.paused,
                   "loop_alive": loop_alive,
                   "wedged": self._wedged,
                   "steps": d.pool.steps,
                   "active": len(d.pool.active_tenants()),
                   "queue": len(d.queue),
                   "requests": len(d.requests),
                   "quarantined": list(d.pool.quarantined)}
            return 200, out, None
        if op == "metrics":
            from ..obs.metrics import REGISTRY
            return (200, REGISTRY.to_prometheus(),
                    "text/plain; version=0.0.4")
        if op == "report":
            with self._lock:
                rep = d.report(list(d._occupancy_traj))
            return 200, rep, None
        if op == "pause" and method == "POST":
            # lint: ok(R9) — GIL-atomic bool store: pause/resume are
            # the handler thread's only writes, the loop re-reads each
            # iteration and /healthz reads lock-free by design; a
            # one-iteration race just delays the pause by one step
            self.paused = True
            return 200, {"paused": True}, None
        if op == "resume" and method == "POST":
            # lint: ok(R9) — same GIL-atomic operator flag as pause
            self.paused = False
            return 200, {"paused": False}, None
        if op == "step" and method == "POST":
            with self._lock:
                # lint: ok(R9) — the ops 'step' RPC deliberately runs
                # one synchronous serving step under the RLock (same
                # work the loop bounds with PARMMG_DEADLINE_SERVE_S);
                # its one subprocess leg is the one-time native build
                st = d.service_once()
            return 200, {"state": st}, None
        if op == "shutdown" and method == "POST":
            # respond first, stop from a fresh thread (shutdown joins
            # the HTTP thread — never from inside a handler)
            threading.Thread(target=self.shutdown,
                             name="parmmg-serve-shutdown",
                             daemon=True).start()
            return 200, {"ok": True}, None
        return 404, {"error": f"unknown op {op!r} ({method})"}, None


# ---------------------------------------------------------------------------
# stdlib HTTP plumbing
# ---------------------------------------------------------------------------
class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):        # route through obs (R3)
        from ..obs import trace as otrace
        otrace.log(3, "serve daemon http: " + fmt % args, err=True)

    def _route(self, method: str) -> None:
        from urllib.parse import parse_qs, urlsplit
        u = urlsplit(self.path)
        op = u.path.strip("/") or "healthz"
        qs = parse_qs(u.query)
        payload: dict = {}
        n = int(self.headers.get("Content-Length") or 0)
        if n:
            try:
                payload = json.loads(self.rfile.read(n).decode("utf-8"))
            except ValueError:
                payload = {}
        code, body, ctype = self.server.pool_daemon.handle_rpc(
            method, op, qs, payload)
        data = body.encode("utf-8") if isinstance(body, str) \
            else json.dumps(body, default=str).encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", ctype or "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        self._route("GET")

    def do_POST(self):
        self._route("POST")
