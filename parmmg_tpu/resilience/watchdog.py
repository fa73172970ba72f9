"""Deadline watchdogs + heartbeat leases: make the WEDGE a ladder rung.

PR 9's fault registry made every failure that *raises* recoverable,
but ParMmg's production failure mode on clusters is the hang: a
collective that never returns, a serving step stuck mid-compile.  The LOWFAILURE contract
promises a usable mesh in *bounded time* (failed_handling,
libparmmg1.c:974-1011) — a hang breaks the "bounded" half without
tripping a single ``except``.  This module converts hangs into the
exception shape the existing ladder already handles:

- :class:`Deadline` — a nestable, polled deadline context for code
  that can check cooperatively (``dl.check()`` raises
  :class:`WatchdogTimeout` once ANY enclosing deadline of the calling
  thread expired; the earliest-armed expired deadline wins);
- :func:`run_with_deadline` — the monitor-thread form for code that
  CANNOT poll (a blocked collective, ``jax.block_until_ready``, a
  wedged RPC): the guarded call runs in a worker thread and the
  caller raises ``WatchdogTimeout`` when it overruns.  SIGALRM-free
  by design: signals do not interrupt jax runtime waits and are
  main-thread-only anyway.  The abandoned worker thread is daemonic
  and harmless by construction at every guarded site — writebacks are
  idempotent and deterministic, so a late commit writes the same
  bytes the retry writes (see the per-site notes at the call sites);
- **first-use grace** (``PARMMG_DEADLINE_GRACE_S``): a site's FIRST
  guarded call gets extra seconds before its deadline fires, so a
  cold XLA compile (minutes, legitimate) is distinguished from a
  wedged warm step (seconds, pathological) without per-site tuning;
- **heartbeat leases** (:func:`beat` / :func:`stale_ranks`): pod
  workers touch a per-rank file inside ``multihost.hot_path``
  sections; the ``scripts/multihost_run.py`` supervisor holds a
  lease per worker and treats a stale lease exactly like a non-zero
  exit — kill the pack, relaunch with ``resume=True``.  A lease only
  becomes revocable AFTER the first beat (a missing file is never
  stale): startup/compile time is covered by the phase timeout, not
  the lease.

An expired deadline raises :class:`WatchdogTimeout`, a plain
``RuntimeError`` subclass, so it enters ``recover.retry_call`` exactly
like an injected fault and the existing ladder (retry -> degrade ->
checkpoint-resume -> LOWFAILURE) handles it unchanged.  Every expiry
bumps ``resilience.watchdog_timeouts`` and emits a
``watchdog.timeout`` trace event.

All deadlines default OFF (knobs ``PARMMG_DEADLINE_*`` = 0): the
zero-config run is bit-neutral and thread-free, and the chaos gate
arms them scenario by scenario.  Host-side stdlib only — no jax
import, no new compile families.
"""
from __future__ import annotations

import os
import threading
import time

__all__ = [
    "Deadline", "WatchdogTimeout", "beat", "deadline_knob",
    "first_use_grace", "heartbeat_config", "record_timeout",
    "run_with_deadline", "stale_ranks",
]


class WatchdogTimeout(RuntimeError):
    """A watchdog deadline expired at ``site`` after ``seconds``.
    Deliberately a plain ``RuntimeError``: ``retry_call`` treats it
    like any transient failure (retry, then the site's degrade rung),
    and ``NEVER_RETRY`` does not match it."""

    def __init__(self, site: str, seconds: float):
        super().__init__(f"watchdog deadline expired at {site} after "
                         f"{seconds:g}s")
        self.site = site
        self.seconds = float(seconds)


def record_timeout(site: str, seconds: float) -> None:
    """Account one watchdog expiry (counter + trace event + log line).
    ``Deadline.check`` / ``run_with_deadline`` call it on their own
    expiries; external enforcers that kill by other means (a
    ``subprocess.run(timeout=)``) call it before raising
    :class:`WatchdogTimeout` so every expiry is visible in ONE
    place regardless of the killing mechanism."""
    from ..obs import trace as otrace
    from ..obs.metrics import REGISTRY
    REGISTRY.counter("resilience.watchdog_timeouts").inc()
    otrace.event("watchdog.timeout", site=site, seconds=float(seconds))
    otrace.log(1, f"  ## resilience: watchdog deadline expired at "
                  f"{site} after {seconds:g}s", err=True)


def deadline_knob(name: str) -> float:
    """Read a ``PARMMG_DEADLINE_*`` / timeout knob in seconds;
    unset/empty/0 means the watchdog is OFF (the default posture:
    deadlines are armed per scenario, never ambient)."""
    try:
        return max(0.0, float(os.environ.get(name, "0") or 0))
    except ValueError:
        return 0.0


# ---------------------------------------------------------------------------
# polled deadlines (cooperative form)
# ---------------------------------------------------------------------------
_LOCAL = threading.local()


def _stack() -> list:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


class Deadline:
    """Nestable polled deadline for the calling thread.

    ``check()`` raises :class:`WatchdogTimeout` when ANY deadline on
    the thread's enter-ordered stack has expired — the outermost
    (earliest-armed) expired one wins, so a tight inner deadline can
    never mask an exhausted outer budget.  ``seconds <= 0`` disarms
    this level (it still nests)."""

    def __init__(self, seconds: float, site: str = "deadline"):
        self.seconds = float(seconds)
        self.site = site
        self._expires_at: float | None = None

    def __enter__(self) -> "Deadline":
        self._expires_at = (time.monotonic() + self.seconds
                            if self.seconds > 0 else None)
        _stack().append(self)
        return self

    def __exit__(self, *exc) -> bool:
        st = _stack()
        if self in st:
            st.remove(self)
        return False

    @property
    def expired(self) -> bool:
        return (self._expires_at is not None
                and time.monotonic() >= self._expires_at)

    def remaining(self) -> float | None:
        """Seconds left on THIS level (None when disarmed)."""
        if self._expires_at is None:
            return None
        return max(0.0, self._expires_at - time.monotonic())

    def check(self) -> None:
        """Raise for the first expired deadline enclosing this thread
        (enter order — the outer budget outranks the inner one)."""
        for d in _stack():
            if d.expired:
                record_timeout(d.site, d.seconds)
                raise WatchdogTimeout(d.site, d.seconds)


# ---------------------------------------------------------------------------
# monitor-thread deadlines (for calls that cannot poll)
# ---------------------------------------------------------------------------
# sites that completed at least one guarded call: their first-use
# compile grace is consumed (a FAILED first call consumes it too — the
# programs it compiled are cached either way)
_FIRST_DONE: set[str] = set()
_FIRST_LOCK = threading.Lock()


def first_use_grace(site: str) -> float:
    """Extra seconds granted to ``site``'s FIRST guarded call: a stuck
    cold compile and a wedged warm step are different diagnoses, and
    only the knob owner knows the compile budget
    (``PARMMG_DEADLINE_GRACE_S``, default 300)."""
    with _FIRST_LOCK:
        if site in _FIRST_DONE:
            return 0.0
    try:
        return max(0.0, float(
            os.environ.get("PARMMG_DEADLINE_GRACE_S", "300") or 300))
    except ValueError:
        return 300.0


def run_with_deadline(fn, seconds: float, site: str):
    """Run ``fn()`` bounded by a wall-clock deadline.

    ``seconds <= 0`` calls inline (watchdog off — the ambient
    default).  Otherwise ``fn`` runs in a daemon worker thread and the
    caller waits ``seconds + first_use_grace(site)``; overrun raises
    :class:`WatchdogTimeout` here while the worker is ABANDONED (its
    late result is discarded).  Guarded sites must therefore be
    idempotent-on-retry — every wired site already is, because the
    retry ladder re-runs them from intact inputs.  The abandoned
    thread rides on the raised exception as ``.thread`` so a caller
    serializing on shared state (the serve daemon's driver lock) can
    wait it out before dispatching again."""
    s = float(seconds)
    if s <= 0:
        return fn()
    box: dict = {}
    done = threading.Event()

    def _target():
        try:
            box["value"] = fn()
        except BaseException as e:            # noqa: BLE001 — relayed
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_target, daemon=True,
                         name=f"parmmg-watchdog-{site}")
    eff = s + first_use_grace(site)
    t.start()
    if not done.wait(eff):
        record_timeout(site, eff)
        exc = WatchdogTimeout(site, eff)
        exc.thread = t
        raise exc
    with _FIRST_LOCK:
        _FIRST_DONE.add(site)
    if "error" in box:
        raise box["error"]
    return box.get("value")


# ---------------------------------------------------------------------------
# heartbeat leases (worker side: beat; supervisor side: stale_ranks)
# ---------------------------------------------------------------------------
_HB = {"last": 0.0}


def heartbeat_config() -> tuple[str, float]:
    """(heartbeat dir, beat interval seconds).  Dir == "" disables —
    ``PARMMG_MH_HEARTBEAT_DIR`` is set by the pod supervisor, never by
    hand."""
    d = os.environ.get("PARMMG_MH_HEARTBEAT_DIR", "")
    try:
        iv = float(os.environ.get("PARMMG_HEARTBEAT_S", "2") or 2)
    except ValueError:
        iv = 2.0
    return d, max(0.05, iv)


def _hb_path(d: str, rank: int) -> str:
    return os.path.join(d, f"hb.{rank}")


def beat(rank: int | None = None) -> str | None:
    """Touch this process's per-rank heartbeat file, throttled to the
    beat interval.  No-op (one env read) unless the supervisor armed
    ``PARMMG_MH_HEARTBEAT_DIR``.  Heartbeats are advisory: an IO
    failure here must never kill the work it is reporting on."""
    d, iv = heartbeat_config()
    if not d:
        return None
    now = time.monotonic()
    if now - _HB["last"] < iv:
        return None
    if rank is None:
        rank = int(os.environ.get("JAX_PROCESS_ID", "0") or 0)
    path = _hb_path(d, rank)
    try:
        os.makedirs(d, exist_ok=True)
        with open(path, "a"):
            pass
        os.utime(path, None)
    except OSError:
        return None
    _HB["last"] = now
    from ..obs.metrics import REGISTRY
    REGISTRY.counter("resilience.heartbeats").inc()
    return path


def stale_ranks(d: str, lease_s: float, ranks,
                now: float | None = None) -> list[int]:
    """Supervisor-side staleness rule (pure, host-only): ranks whose
    lease expired.  A lease is revocable only AFTER the first beat —
    the heartbeat file must EXIST and be older than ``lease_s``.  A
    rank that never beat is never stale (startup + cold compile run
    before the first ``hot_path`` beat; the phase timeout covers a
    worker that dies there).  ``lease_s <= 0`` disables."""
    out: list[int] = []
    if lease_s <= 0:
        return out
    t = time.time() if now is None else now
    for r in ranks:
        try:
            m = os.stat(_hb_path(d, int(r))).st_mtime
        except OSError:
            continue
        if t - m > lease_s:
            out.append(int(r))
    return out
