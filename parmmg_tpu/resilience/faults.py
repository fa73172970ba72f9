"""Named-faultpoint registry: provoke the REAL failure paths on demand.

Every recovery path in this codebase was born from an actual incident
(device dispatches kernel-faulting late in a session, checkpoint disks
filling up) — but none of them
could be *provoked* without waiting for the hardware to oblige.  This
module arms named fault sites through one env knob so the degrade
ladder is exercised by CI (``scripts/chaos_check.py``), not simulated
with mocks:

    PARMMG_FAULT=site[:trigger][,site[:trigger]...]

``site`` is one of :data:`SITES`.  ``trigger`` is ``;``-separated
rules (all must pass for the site to fire):

- *(none)*      — fire on every hit;
- ``nth-N``     — fire on the Nth hit only (1-based; ``N`` alone works);
- ``every-K``   — fire on every Kth hit;
- ``p=0.x``     — fire with probability x per hit (``seed=N`` makes the
  sequence reproducible; default seed 0);
- ``key=S``     — fire only on hits whose ``key`` argument equals S
  (e.g. a specific serve tenant); non-matching hits do not advance the
  site's hit counter;
- ``hang=S``    — ACTION modifier: when the rule fires, the site
  sleeps S seconds and then RETURNS instead of raising — the testable
  stand-in for a wedged collective/worker (the failure mode deadline
  watchdogs and heartbeat leases exist for, resilience/watchdog.py).
  Composes with the triggers above.

Exception fidelity: :func:`faultpoint` raises the site's REAL failure
shape — ``XlaRuntimeError`` for device-dispatch sites, ``OSError`` for
IO sites — so ``except`` clauses in the recovery code are hit exactly
as they would be by the hardware.  Sites whose real failure is a flag,
not an exception (the analysis KS-overflow fallback), use
:func:`fault_trigger` and return a bool.
"""
from __future__ import annotations

import dataclasses
import os
import random
import threading
import time

__all__ = [
    "FAULTS", "FaultRegistry", "FaultRule", "SITES", "fault_trigger",
    "faultpoint", "parse_fault_spec",
]

# the injectable sites and the exception shape each raises
# (xla = device dispatch failure, os = IO failure, flag = non-exception
# trigger consumed by the caller)
SITES = {
    "dispatch.chunk": "xla",
    "halo.exchange": "xla",
    "multihost.exchange": "xla",
    "analysis.ks_overflow": "flag",
    "serve.slot_step": "xla",
    "serve.daemon_rpc": "os",
    "io.checkpoint": "os",
}

@dataclasses.dataclass
class FaultRule:
    """One armed site's trigger: all set conditions must pass."""
    site: str
    nth: int | None = None       # fire on the Nth matching hit only
    every: int | None = None     # fire on every Kth matching hit
    p: float | None = None       # fire with probability p per hit
    seed: int = 0
    key: str | None = None       # fire only when the hit key matches
    hang: float | None = None    # ACTION: sleep S then return, no raise

    def __post_init__(self):
        self._rng = random.Random(self.seed)
        self._hits = 0

    def fires(self, key: str | None) -> bool:
        if self.key is not None and key != self.key:
            return False
        self._hits += 1
        # ALL set conditions must pass (the documented ';' semantics).
        # The probability draw happens on every matching hit so the
        # seeded sequence is independent of the other conditions.
        ok = True
        if self.p is not None:
            ok = self._rng.random() < self.p
        if self.nth is not None:
            ok = ok and self._hits == self.nth
        if self.every is not None:
            ok = ok and self._hits % self.every == 0
        return ok


def parse_fault_spec(spec: str) -> dict:
    """``PARMMG_FAULT`` grammar -> {site: FaultRule}.  Raises
    ValueError on unknown sites or malformed triggers (a typo'd chaos
    knob must fail loudly, not silently inject nothing)."""
    rules: dict[str, FaultRule] = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        site, _, trig = part.partition(":")
        if site not in SITES:
            raise ValueError(
                f"unknown fault site {site!r} (known: {sorted(SITES)})")
        kw: dict = {}
        for tok in filter(None, (t.strip() for t in trig.split(";"))):
            if tok.startswith("nth-"):
                kw["nth"] = int(tok[4:])
            elif tok.isdigit():
                kw["nth"] = int(tok)
            elif tok.startswith("every-"):
                kw["every"] = int(tok[6:])
            elif tok.startswith("p="):
                kw["p"] = float(tok[2:])
            elif tok.startswith("seed="):
                kw["seed"] = int(tok[5:])
            elif tok.startswith("key="):
                kw["key"] = tok[4:]
            elif tok.startswith("hang="):
                kw["hang"] = float(tok[5:])
            else:
                raise ValueError(
                    f"unparseable fault trigger {tok!r} in {part!r}")
        for f in ("nth", "every"):
            if kw.get(f) is not None and kw[f] < 1:
                raise ValueError(f"{f} must be >= 1 in {part!r}")
        if kw.get("hang") is not None and kw["hang"] <= 0:
            raise ValueError(f"hang must be > 0 seconds in {part!r}")
        rules[site] = FaultRule(site=site, **kw)
    return rules


class FaultRegistry:
    """Lazy env-armed registry; hit counters persist for the lifetime
    of one parsed spec (re-parsed when PARMMG_FAULT changes)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._raw: str | None = None
        self._rules: dict[str, FaultRule] = {}

    def reset(self) -> None:
        """Drop the parsed spec + counters (re-reads env on next hit).
        Tests and the chaos gate call this between scenarios."""
        with self._lock:
            self._raw = None
            self._rules = {}

    def _resolve(self) -> dict:
        raw = os.environ.get("PARMMG_FAULT", "")
        if raw != self._raw:
            self._raw = raw
            self._rules = parse_fault_spec(raw) if raw else {}
        return self._rules

    def armed(self) -> bool:
        with self._lock:
            return bool(self._resolve())

    def fired_rule(self, site: str,
                   key: str | None = None) -> FaultRule | None:
        """The armed rule for ``site`` when it fires on this hit, else
        None.  Callers needing the ACTION (raise vs ``hang``) use this;
        :meth:`should_fire` stays the boolean form."""
        with self._lock:
            rule = self._resolve().get(site)
            if rule is None:
                return None
            if rule.fires(None if key is None else str(key)):
                return rule
            return None

    def should_fire(self, site: str, key: str | None = None) -> bool:
        return self.fired_rule(site, key) is not None


FAULTS = FaultRegistry()


def _site_exception(site: str, key: str | None):
    kind = SITES.get(site, "xla")
    msg = (f"INTERNAL: injected fault at {site}"
           + (f" (key={key})" if key is not None else "")
           + " [PARMMG_FAULT]")
    if kind == "os":
        return OSError(msg)
    # the device-dispatch failure shape: the exact class jax raises on
    # a crashed/overflowed device program (falls back to RuntimeError
    # when jaxlib is absent — host-only test environments)
    try:
        from jax._src.lib import xla_client
        return xla_client.XlaRuntimeError(msg)
    except Exception:
        return RuntimeError(msg)


def _record(site: str, key: str | None,
            hang: float | None = None) -> None:
    from ..obs import trace as otrace
    from ..obs.metrics import REGISTRY
    REGISTRY.counter("resilience.faults_injected").inc()
    otrace.event("fault.injected", site=site,
                 **({} if key is None else {"key": str(key)}),
                 **({} if hang is None else {"hang_s": float(hang)}))


def faultpoint(site: str, key: str | None = None) -> None:
    """Raise the site's real exception type when armed and firing.
    Free when PARMMG_FAULT is unset (one dict lookup).  A firing rule
    with ``hang=S`` sleeps S seconds and returns instead — the wedge,
    not the crash: nothing raises, and only a deadline watchdog or
    heartbeat lease (resilience/watchdog.py) can notice."""
    rule = FAULTS.fired_rule(site, key)
    if rule is None:
        return
    if rule.hang is not None:
        _record(site, key, hang=rule.hang)
        time.sleep(rule.hang)
        return
    _record(site, key)
    raise _site_exception(site, key)


def fault_trigger(site: str, key: str | None = None) -> bool:
    """Flag-style sites (the real failure is a condition, not an
    exception — e.g. the analysis KS-overflow fallback): True when the
    armed fault fires, so the caller takes its real degraded branch.
    A ``hang=S`` rule sleeps and returns False — a wedge delays the
    site, it does not flip its condition."""
    rule = FAULTS.fired_rule(site, key)
    if rule is None:
        return False
    if rule.hang is not None:
        _record(site, key, hang=rule.hang)
        time.sleep(rule.hang)
        return False
    _record(site, key)
    return True
