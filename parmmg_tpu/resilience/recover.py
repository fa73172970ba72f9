"""Retry/backoff wrapper + the ordered escalation ladder.

The degrade behavior of this stack predates this module — the driver
caught OOM, the dist
path fell back from device to host analysis — but each path was its
own ad-hoc ``except`` with its own (or no) reporting.  This module is
the shared spine:

- :func:`retry_call` — bounded retries with exponential backoff and an
  optional wall-clock deadline, knobs ``PARMMG_RETRY_MAX`` (default
  2 retries after the first failure), ``PARMMG_RETRY_BASE_S`` (default
  0.05 s, doubled per attempt) and ``PARMMG_RETRY_DEADLINE_S`` (0 =
  off).  Exhaustion raises :class:`RetryBudgetExhausted` (the original
  failure chained as ``__cause__``) — the signal the driver converts
  into a ``PMMG_LOWFAILURE`` conforming save;
- :data:`LADDER` + :func:`ladder_step` — the documented escalation
  order every degrade path reports through.  Each step taken emits an
  obs trace event (``resilience.ladder``) and bumps a
  ``resilience.<step>`` counter, so a run's failure story is readable
  from its trace/metrics instead of scattered stderr lines.

Ladder order (least to most degraded; each step preserves the
conforming-mesh invariant):

    retry          re-run the failed unit (chunk dispatch / worker /
                   band exchange)
    mh_allgather   pod band-exchange collective failed -> metered
                   pull_host allgather (bit-identical values, counted
                   bytes — parallel/pod.py escape hatch)
    halo_dense     packed halo exchange failed -> dense layout retry
    host_analysis  device analysis refresh failed/overflowed -> host
    merged_polish  grouped polish dispatch kept failing -> skip the
                   rest, the caller's merged-mesh polish + repair tail
                   covers quality
    lowfailure     restore the last conforming state, return
                   PMMG_LOWFAILURE (failed_handling,
                   libparmmg1.c:974-1011)
"""
from __future__ import annotations

import os
import time

__all__ = [
    "LADDER", "RetryBudgetExhausted", "WorkerExitError",
    "is_deterministic", "ladder_step", "retry_call", "retry_env",
]

LADDER = ("retry", "mh_allgather", "halo_dense", "host_analysis",
          "merged_polish", "lowfailure")

# deterministic failures must not be retried: re-running the identical
# program reproduces the identical capacity overflow, and an error
# raised while tracing, lowering or compiling (Mosaic and XLA refusals
# arrive as NotImplementedError / ValueError / TypeError) cannot change
# on a retry
NEVER_RETRY = (MemoryError, NotImplementedError, ValueError, TypeError)

# XlaRuntimeError carries its absl status as the message prefix; only
# these can go away on a retry (INTERNAL is what a faulted device
# program reports — but a refused compile says INTERNAL too)
_TRANSIENT_STATUS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
                     "CANCELLED", "INTERNAL", "UNKNOWN")


def is_deterministic(e: BaseException) -> bool:
    """True for failures a retry cannot change (see NEVER_RETRY); every
    other exception is treated as transient."""
    if isinstance(e, NEVER_RETRY):
        return True
    if type(e).__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
        msg = str(e)
        return (not msg.startswith(_TRANSIENT_STATUS)
                or "Mosaic" in msg or "compil" in msg.lower())
    return False


class RetryBudgetExhausted(RuntimeError):
    """All retries for ``site`` failed; ``__cause__`` is the last
    failure.  Callers translate this into the next ladder step
    (typically ``lowfailure`` at the driver)."""

    def __init__(self, site: str, attempts: int):
        super().__init__(
            f"retry budget exhausted at {site} after {attempts} "
            "attempt(s)")
        self.site = site
        self.attempts = attempts


class WorkerExitError(RuntimeError):
    """A subprocess worker exited non-zero."""

    def __init__(self, site: str, returncode: int, stderr: str = ""):
        tail = stderr[-2000:] if stderr else ""
        super().__init__(f"{site} worker exited rc={returncode}"
                         + (f"\n{tail}" if tail else ""))
        self.site = site
        self.returncode = returncode
        self.stderr = stderr


def retry_env() -> tuple[int, float, float]:
    """(max_retries, backoff base seconds, deadline seconds)."""
    mx = int(os.environ.get("PARMMG_RETRY_MAX", "2") or 2)
    base = float(os.environ.get("PARMMG_RETRY_BASE_S", "0.05") or 0.05)
    dl = float(os.environ.get("PARMMG_RETRY_DEADLINE_S", "0") or 0)
    return max(0, mx), max(0.0, base), max(0.0, dl)


def ladder_step(step: str, site: str = "", detail: str = "") -> None:
    """Record one escalation-ladder step: trace event + counter + an
    imprim-gated warning line (the one print path, obs/trace.py)."""
    from ..obs import trace as otrace
    from ..obs.metrics import REGISTRY
    if step not in LADDER:
        raise ValueError(f"unknown ladder step {step!r} "
                         f"(ladder: {LADDER})")
    REGISTRY.counter(f"resilience.{step}").inc()
    otrace.event("resilience.ladder", step=step, site=site,
                 detail=detail[:500])
    otrace.log(1, f"  ## resilience: {step}"
                  + (f" at {site}" if site else "")
                  + (f" ({detail[:200]})" if detail else ""), err=True)


def retry_call(fn, site: str, max_retries: int | None = None,
               base_s: float | None = None,
               deadline_s: float | None = None,
               initial_failure: BaseException | None = None):
    """Call ``fn()`` with up to ``max_retries`` re-attempts after a
    failure, exponential backoff between attempts, and an optional
    wall-clock deadline that stops retrying early.

    ``initial_failure``: the caller already made (and lost) attempt 0
    inline — e.g. the pipelined chunk dispatch, whose first attempt
    rides the fast path — so only the RETRY budget remains.  With
    ``PARMMG_RETRY_MAX=0`` that exhausts immediately: fail-fast mode.

    Deterministic failures (:func:`is_deterministic`: capacity signals,
    lowering and compile errors) pass straight through."""
    env_mx, env_base, env_dl = retry_env()
    mx = env_mx if max_retries is None else max(0, int(max_retries))
    base = env_base if base_s is None else max(0.0, float(base_s))
    dl = env_dl if deadline_s is None else max(0.0, float(deadline_s))
    t0 = time.monotonic()
    last: BaseException | None = initial_failure
    attempts = 1 if initial_failure is not None else 0
    retries_left = mx
    while True:
        if last is not None:
            if is_deterministic(last):
                raise last
            if retries_left <= 0 or (dl and time.monotonic() - t0 >= dl):
                from ..obs.metrics import REGISTRY
                REGISTRY.counter("resilience.retry_exhausted").inc()
                raise RetryBudgetExhausted(site, attempts) from last
            # backoff then re-attempt (attempt k sleeps base * 2^(k-1))
            ladder_step("retry", site=site, detail=repr(last))
            if base > 0:
                time.sleep(min(base * (2 ** (attempts - 1)), 30.0))
            retries_left -= 1
        try:
            return fn()
        except Exception as e:
            if is_deterministic(e):
                raise
            last = e
            attempts += 1
