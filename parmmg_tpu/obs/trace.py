"""Structured trace emitter + run context + the span primitive.

One record per completed span (not begin/end pairs): replay is a plain
per-name sum, the file stays half the size, and a crashed run loses at
most the spans still open.  Records are dicts; the run context
(:func:`set_context` for process-wide keys like the run id and backend,
:func:`context` for scoped overlays like pass/block/chunk/tenant) is
folded into every record at emit time, so a trace line is
self-describing without a join.

:class:`span` is the ONE way a span is made (``utils.timers.Timers``
scopes go through it, so every ``with tim(...)`` is a span for free).
A span record holds

``kind`` ``name`` ``dur`` ``count`` ``ts``  as ever: seconds, and the
    wall clock at close;
``id`` ``parent``  a process-unique int, and the ``id`` of the span
    that was open on this thread when this one opened (absent for a
    root), so the records of a job form a tree under its ``run`` span;
``t0``  its start on ``time.perf_counter_ns()``'s clock (``dur`` is
    taken from the same clock);
``run`` and the rest of the context; ``tim`` for a Timers scope (the
    replay filter); the counts given as fields at open or at close
    (:meth:`span.set`): a wave's ``collapse``/``swap``/``moved``, a
    split's ``groups``/``capT``.

For as long as it is open a span also holds a
``jax.profiler.TraceAnnotation`` of the same name, unconditionally once
``jax`` is imported (an unarmed one costs a third of a microsecond): a
capture started by anyone — the benchmark, ``PARMMG_PROFILE_DIR``, an
operator's TensorBoard — then carries every program span on the
profiler's own clock beside the device's ops, and no offset between two
clocks is computed anywhere.  :func:`emit_span` folds in a duration
measured elsewhere (``Timers.add``): same ``id``/``parent``/``run``,
``ext`` where it applies, no ``t0`` and no annotation, because there is
no interval to open.  Names are fixed strings; pass, block, chunk and
wave numbers are fields or :func:`context`, never part of a name.

Sinks: an always-on ring buffer (``PARMMG_TRACE_RING`` records, default
4096 — the ``PMMG_ctim`` slots' bounded-memory role) and, when
``PARMMG_TRACE=path`` is set (or :meth:`Tracer.configure` is called), a
JSONL file appended line-by-line.  :func:`replay_totals` reconstructs
exactly one Timers registry's ``report()`` from the stream.

Device timelines: ``PARMMG_PROFILE_DIR=<dir>`` is the operator's one
switch: :func:`profile_capture` (entered by ``driver.parmmg_run``)
holds a ``jax.profiler`` capture over one whole run, staging to tail,
and when it closes hands the capture to ``obs.devtime``, which joins the
device's op events with the scopes the executable carries and emits one
``device_phases`` event: device seconds by phase of the cycle.
:func:`scope` wraps ``jax.named_scope`` (XLA op metadata): every stage
of a cycle, of a polish wave and of a fem pass sits under one
(``cyc.*``, ``pol.*``, ``fem.*``; the table makers under ``tab.*``).

:func:`log` is the one verbosity-gated print path (the reference's
``imprim`` levels, core.constants.PMMG_VERB_*): gated output AND an
always-emitted trace record, so ``-v`` output and the trace stream
cannot drift.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext

__all__ = [
    "TRACER", "Tracer", "context", "current_context", "current_span",
    "emit_span", "event", "log", "new_run", "profile_capture",
    "replay_totals", "scope", "set_context", "set_verbosity", "span",
    "verbosity",
]


# ---------------------------------------------------------------------------
# run context
# ---------------------------------------------------------------------------
_BASE: dict = {}
_TLS = threading.local()


def set_context(**kv) -> None:
    """Merge process-wide context keys (run id, backend, tenant...).
    ``None`` deletes a key."""
    for k, v in kv.items():
        if v is None:
            _BASE.pop(k, None)
        else:
            _BASE[k] = v


def new_run(backend: str | None = None) -> str:
    """Start a fresh run context: new run id, optional backend tag
    (defaulted from an already-imported jax — never imports it)."""
    import uuid
    if backend is None:
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                backend = jax.default_backend()
            except Exception:
                backend = None
    _BASE.clear()
    rid = uuid.uuid4().hex[:12]
    set_context(run=rid, backend=backend)
    return rid


@contextmanager
def context(**kv):
    """Thread-local scoped context overlay (pass/cycle/block/chunk/
    tenant...) folded into every record emitted inside the scope."""
    stk = getattr(_TLS, "stack", None)
    if stk is None:
        stk = _TLS.stack = []
    stk.append({k: v for k, v in kv.items() if v is not None})
    try:
        yield
    finally:
        stk.pop()


def current_context() -> dict:
    out = dict(_BASE)
    for d in getattr(_TLS, "stack", ()) or ():
        out.update(d)
    return out


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------
class Tracer:
    """Ring buffer + optional JSONL sink.  Thread-safe; the env sink
    (``PARMMG_TRACE``) is resolved lazily on first emit so importing
    this module never opens files."""

    def __init__(self, ring: int | None = None, path: str | None = None):
        if ring is None:
            ring = int(os.environ.get("PARMMG_TRACE_RING", "4096")
                       or 4096)
        self.ring: deque = deque(maxlen=max(1, ring))
        self._lock = threading.Lock()
        self._emitted = 0
        self._path = path
        self._fh = None
        self._env_checked = path is not None

    def _sink(self):
        if not self._env_checked:
            self._env_checked = True
            p = os.environ.get("PARMMG_TRACE", "")
            if p:
                self._path = p
        if self._path and self._fh is None:
            try:
                self._fh = open(self._path, "a", buffering=1)
            except OSError:
                self._path = None
        return self._fh

    def configure(self, path: str | None = None,
                  ring: int | None = None) -> None:
        """Re-point the JSONL sink (None = ring only); resets the env
        resolution so tests and the obs gate control the sink
        explicitly."""
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None
            self._path = path
            self._env_checked = True
            if ring is not None:
                self.ring = deque(maxlen=max(1, ring))

    def reset(self) -> None:
        with self._lock:
            self.ring.clear()
            self._emitted = 0

    def emit(self, rec: dict) -> None:
        rec.setdefault("ts", round(time.time(), 6))
        for k, v in current_context().items():
            rec.setdefault(k, v)
        with self._lock:
            self._emitted += 1
            self.ring.append(rec)
            fh = self._sink()
            if fh is not None:
                try:
                    fh.write(json.dumps(rec) + "\n")
                except (OSError, TypeError, ValueError):
                    pass

    def summary(self, top: int = 8) -> dict:
        """Compact trace digest for artifacts: emit/drop counts, sink,
        and the top span totals seen in the ring."""
        with self._lock:
            recs = list(self.ring)
            emitted = self._emitted
        tot: dict[str, float] = {}
        for r in recs:
            if r.get("kind") == "span":
                tot[r["name"]] = tot.get(r["name"], 0.0) \
                    + float(r.get("dur", 0.0))
        tops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return {"events": emitted, "ring": len(recs),
                "dropped": max(0, emitted - len(recs)),
                "sink": self._path or "",
                "top_spans_s": {k: round(v, 4) for k, v in tops}}


TRACER = Tracer()


# ---------------------------------------------------------------------------
# the span primitive
# ---------------------------------------------------------------------------
_SPAN_IDS = itertools.count(1)      # next() is atomic under the GIL
_ANNOTATION = None                  # jax.profiler.TraceAnnotation, once seen


def _open_spans() -> list:
    stk = getattr(_TLS, "spans", None)
    if stk is None:
        stk = _TLS.spans = []
    return stk


def current_span() -> int | None:
    """``id`` of the innermost span open on this thread."""
    stk = getattr(_TLS, "spans", None)
    return stk[-1] if stk else None


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name``, or None while
    jax is not imported (host-only contexts stay jax-free)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        prof = getattr(jax, "profiler", None)
        _ANNOTATION = getattr(prof, "TraceAnnotation", None)
        if _ANNOTATION is None:
            return None
    return _ANNOTATION(name)


class span:
    """``with span(name, **fields) as sp:`` measures, annotates the
    profiler's timeline and emits one record at close (module
    docstring).  ``sp.set(**fields)`` adds the counts known only at the
    end; ``sp.dur`` holds the seconds once closed."""

    __slots__ = ("name", "fields", "id", "parent", "t0", "dur", "_ann")

    def __init__(self, name: str, **fields):
        self.name = name
        self.fields = fields
        self.dur = 0.0

    def set(self, **fields) -> None:
        self.fields.update(fields)

    def __enter__(self):
        stk = _open_spans()
        self.parent = stk[-1] if stk else None
        self.id = next(_SPAN_IDS)
        stk.append(self.id)
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.dur = (time.perf_counter_ns() - self.t0) / 1e9
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stk = _open_spans()
        # a span leaked open by a generator or a thread hand-off must
        # not become every later span's parent: unwind to this one
        while stk and stk.pop() != self.id:
            pass
        rec = {"kind": "span", "name": self.name, "dur": self.dur,
               "count": 1, "id": self.id, "t0": self.t0}
        if self.parent is not None:
            rec["parent"] = self.parent
        rec.update(self.fields)
        TRACER.emit(rec)
        return False


def emit_span(name: str, dur: float, count: int = 1,
              tim: int | None = None, ext: bool = False) -> None:
    """Fold in one span measured elsewhere (``Timers.add``; the SPMD
    loop's segments).  ``tim``: emitting Timers instance id (the replay
    filter); ``ext``: segment absorbed from another component's
    measurement (Timers.add outside any scope)."""
    rec = {"kind": "span", "name": name, "dur": round(float(dur), 9),
           "count": int(count), "id": next(_SPAN_IDS)}
    parent = current_span()
    if parent is not None:
        rec["parent"] = parent
    if tim is not None:
        rec["tim"] = tim
    if ext:
        rec["ext"] = True
    TRACER.emit(rec)


def event(name: str, **fields) -> None:
    """One point record; ``parent`` is the span open on this thread."""
    rec = {"kind": "event", "name": name}
    parent = current_span()
    if parent is not None:
        rec["parent"] = parent
    rec.update(fields)
    TRACER.emit(rec)


def replay_totals(source, tim: int | None = None
                  ) -> tuple[dict, dict]:
    """Reconstruct per-phase (total seconds, counts) from a trace — a
    JSONL path or an iterable of records.  ``tim`` filters to one
    Timers instance so the result is comparable to that instance's
    ``acc``/``count`` (the ``--obs`` gate's replay check).  Unparseable
    lines are skipped (a crashed writer may truncate the last one)."""
    if isinstance(source, (str, os.PathLike)):
        recs = []
        with open(source) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue
    else:
        recs = list(source)
    tot: dict[str, float] = {}
    cnt: dict[str, int] = {}
    for r in recs:
        if r.get("kind") != "span":
            continue
        if tim is not None and r.get("tim") != tim:
            continue
        n = r["name"]
        tot[n] = tot.get(n, 0.0) + float(r.get("dur", 0.0))
        cnt[n] = cnt.get(n, 0) + int(r.get("count", 1))
    return tot, cnt


# ---------------------------------------------------------------------------
# verbosity-gated logging (imprim analogue)
# ---------------------------------------------------------------------------
_VERBOSITY = [int(os.environ.get("PARMMG_VERBOSE", "1") or 1)]


def set_verbosity(v: int) -> None:
    """Set the process verbosity (the reference's ``imprim``; the
    driver calls this from ``info.imprim`` at run start)."""
    _VERBOSITY[0] = int(v)


def verbosity() -> int:
    return _VERBOSITY[0]


def log(level: int, msg: str, verbose: int | None = None,
        err: bool = False) -> bool:
    """Verbosity-gated print + unconditional trace record.

    ``level``: the imprim threshold (core.constants.PMMG_VERB_*).
    ``verbose``: optional local verbosity (the dist/groups drivers
    carry one on the same scale) — overrides the process value.  The
    record is emitted whether or not the line printed (``shown``
    flags it), so the trace stream and the -v output cannot drift.
    Returns whether the line printed."""
    gate = _VERBOSITY[0] if verbose is None else int(verbose)
    shown = gate >= level
    TRACER.emit({"kind": "log", "lvl": int(level), "msg": str(msg),
                 "shown": bool(shown)})
    if shown:
        print(msg, file=sys.stderr if err else sys.stdout)
    return shown


# ---------------------------------------------------------------------------
# jax profiler integration (the operator's capture + op-metadata scopes)
# ---------------------------------------------------------------------------
@contextmanager
def profile_capture():
    """``PARMMG_PROFILE_DIR=<dir>``: hold one ``jax.profiler`` capture
    over the block (``driver.parmmg_run`` wraps a whole run in it),
    closed on every way out.  Yields whether a capture was started: not
    when the variable is unset, nor when the profiler refuses (somebody
    else's capture is already running — that one then holds the run's
    spans all the same, since spans annotate unconditionally)."""
    d = os.environ.get("PARMMG_PROFILE_DIR", "")
    if not d:
        yield False
        return
    try:
        import jax
        os.makedirs(d, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        # host TraceMes (the spans) and device ops, no Python frames:
        # a whole run of them makes a capture nobody can open
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
    except Exception as e:
        log(0, f"obs: profiler capture failed to arm ({e!r})", err=True)
        yield False
        return
    event("profile_start", dir=d)
    try:
        yield True
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            log(0, f"obs: profiler capture failed to close ({e!r})",
                err=True)
        event("profile_stop", dir=d)
        # stderr: stdout is the artifact channel of every emitting script
        log(1, f"obs: profiler trace written to {d}", err=True)
        # the program's own digest of the capture it made: device
        # seconds by phase, ONE ``device_phases`` event (obs/devtime).
        # Only here: a run without the variable never imports the module
        try:
            from . import devtime
            devtime.digest_run(d)
        except Exception as e:
            log(0, f"obs: no digest of the capture ({e!r})", err=True)


def scope(name: str):
    """``jax.named_scope`` wrapper for traced code: XLA ops inside
    carry ``name`` on the device timeline.  Nullcontext when jax is not
    imported (host-only contexts must stay jax-free)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return nullcontext()
    try:
        return jax.named_scope(name)
    except Exception:
        return nullcontext()
