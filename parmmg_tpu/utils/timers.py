"""Hierarchical wall-clock timers (mytime/chrono/printim analogue).

The reference tracks per-phase times in ``PMMG_ctim[TIMEMAX]`` slots with
verbosity-gated prints (parmmg.c:35,91; libparmmg1.c:636-948).  Here a
small nestable timer registry with the same reporting role.

Every scope IS an ``obs.trace.span`` (the one span primitive: id,
parent, start on the profiler's clock, a timeline annotation of the
same name) carrying this instance's ``trace_id``, so the JSONL trace
replays to exactly this registry's totals
(``obs.trace.replay_totals(path, tim=timers.trace_id)`` — the
``run_tests.sh --obs`` gate's check).  Emission is a ring-buffer append
when no sink is armed: safe in the chunk-pipeline hot loop.

The compile ledger (utils/compilecache.py) is re-exported here so the
drivers' reporting layer has ONE import surface for both wall-clock and
compile accounting: ``Timers.report`` for phases,
``format_ledger``/``ledger_snapshot`` for XLA compile churn.
"""
from __future__ import annotations

import itertools
from contextlib import contextmanager

from ..obs.trace import emit_span, span
from .compilecache import (                                    # noqa: F401
    LEDGER, format_ledger, ledger_snapshot, ledger_violations,
    reset_ledger)


class Timers:
    _IDS = itertools.count(1)

    def __init__(self):
        self.acc: dict[str, float] = {}
        self.count: dict[str, int] = {}
        self._stack: list[str] = []
        # paths absorbed via add() OUTSIDE any active scope: externally
        # measured segments, rendered distinctly by report()
        self.external: set[str] = set()
        # stable id stamped on every emitted span (the replay filter)
        self.trace_id: int = next(Timers._IDS)

    @contextmanager
    def __call__(self, name: str):
        path = "/".join(self._stack + [name])
        self._stack.append(name)
        sp = span(path, tim=self.trace_id)
        try:
            with sp:
                yield sp        # the caller may ``set`` fields on it
        finally:
            self._stack.pop()
            # the very float the record carries (whole ns / 1e9):
            # accumulator and replayed stream agree bit-for-bit (the
            # --obs gate's replay==report contract)
            self.acc[path] = self.acc.get(path, 0.0) + sp.dur
            self.count[path] = self.count.get(path, 0) + 1

    def add(self, name: str, seconds: float, count: int = 1) -> None:
        """Fold an externally-measured duration into the registry at
        the current nesting path.  The grouped chunk pipeline
        (parallel/groups._pipeline_chunks) measures its
        upload/compute/download/writeback segments on a local Timers
        and absorbs them into the driver's reporting instance here.

        Called OUTSIDE any active ``with tim(...)`` scope, the segment
        is tagged *external* (it was measured by another component, not
        timed here): ``report()`` renders it with an ``[absorbed]``
        marker instead of passing it off as a phase of this registry,
        and the emitted span carries ``ext=True``."""
        ext = not self._stack
        path = "/".join(self._stack + [name])
        if ext:
            self.external.add(path)
        # same ns rounding as the scope exit: acc == replayed spans
        seconds = round(float(seconds), 9)
        self.acc[path] = self.acc.get(path, 0.0) + seconds
        self.count[path] = self.count.get(path, 0) + int(count)
        emit_span(path, seconds, count=int(count),
                  tim=self.trace_id, ext=ext)

    def report(self, min_s: float = 0.0) -> str:
        lines = []
        for k in sorted(self.acc):
            if self.acc[k] < min_s:
                continue
            depth = k.count("/")
            mark = "  [absorbed]" if k in self.external else ""
            lines.append(f"{'  ' * depth}{k.split('/')[-1]:28s} "
                         f"{self.acc[k]:9.3f}s  x{self.count[k]}{mark}")
        return "\n".join(lines)
