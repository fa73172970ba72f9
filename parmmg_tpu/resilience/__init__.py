"""Fault injection + self-healing recovery for the adapt/serve stack.

The reference's graded-failure contract (``failed_handling``,
libparmmg1.c:974-1011) is that the library never dies holding user
data: it degrades to ``PMMG_LOWFAILURE`` and hands back a conforming
mesh.  This package turns the reproduction's scattered implicit
degrade paths (driver OOM catches, the serve timeout expiry) into one explicit, injectable, gated subsystem:

- :mod:`~parmmg_tpu.resilience.faults` — a named-faultpoint registry
  armed via ``PARMMG_FAULT=site[:trigger]``.  Each site raises its
  REAL failure shape (``XlaRuntimeError`` for device dispatches,
  ``OSError`` for checkpoint IO) so the recovery code below is exercised, never
  simulated;
- :mod:`~parmmg_tpu.resilience.recover` — the deadline + retry +
  exponential-backoff wrapper (``PARMMG_RETRY_MAX`` /
  ``PARMMG_RETRY_BASE_S`` / ``PARMMG_RETRY_DEADLINE_S``) and the
  ordered escalation ladder the degrade paths report through
  (``LADDER``: retry -> packed->dense halo -> device->host analysis ->
  grouped->merged polish -> LOWFAILURE), each step an obs trace event
  plus a ``resilience.*`` metrics counter;
- :mod:`~parmmg_tpu.resilience.checkpoint` — pass-level
  checkpoint/resume (``PARMMG_CKPT_DIR`` / ``PARMMG_CKPT_EVERY``): the
  grouped outer loop snapshots (mesh, met, displaced part) after each
  completed pass, plus the merge-free ``stacked_to_distributed_files``
  shard snapshot of the pre-merge stacked state — the reference's
  ``-distributed-output`` checkpoint role.  ``cli.py -resume`` and
  ``scripts/scale_big.py --resume`` restart a killed run from the last
  completed pass, bit-identical to an uninterrupted run.  The
  crash-loop breaker (``crash_loop``, ``PARMMG_RESUME_MAX``) bounds
  the resume ladder itself: a pass that deterministically kills its
  worker is escalated past instead of resumed forever;
- :mod:`~parmmg_tpu.resilience.watchdog` — the HANG mirror of the
  fault registry: deadline watchdogs (``Deadline`` /
  ``run_with_deadline``, knobs ``PARMMG_DEADLINE_*``) convert a
  wedged dispatch/exchange/serve-step into a
  ``WatchdogTimeout`` that enters ``retry_call`` like any injected
  fault, and per-rank heartbeat leases (``beat`` / ``stale_ranks``,
  ``PARMMG_HEARTBEAT_*``) let the pod supervisor treat a stalled
  worker like a crashed one (kill the pack, relaunch with resume).
  Provoked on demand via the ``hang=S`` fault action; soaked by
  ``scripts/chaos_soak.py``.

Everything here is host-side bookkeeping: no jax import at module
scope, zero new compile families on the fault-free path (gated by
``scripts/run_tests.sh --chaos``).
"""
from .faults import FAULTS, fault_trigger, faultpoint        # noqa: F401
from .recover import (LADDER, RetryBudgetExhausted,          # noqa: F401
                      ladder_step, retry_call)
from .watchdog import (Deadline, WatchdogTimeout,            # noqa: F401
                       run_with_deadline)
