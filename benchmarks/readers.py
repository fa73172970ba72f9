"""What the small readers under ``end_to_end/`` and ``layer_metrics/``
share.

A reader is ``read(run) -> float | None``.  ``run`` holds ``setup_s``,
``chips``, the window's ``jobs`` (each with its wall ``seconds``, the
second it ended at from the window's start ``end_s``, the program's
phase seconds ``phases``, its spans, the increase of its counters
``counters`` and the checker's ``numbers``), ``trace``
(trace_reduce.reduce_dir's result for the traced job; None in an
untraced run and in a CPU rehearsal), ``peaks`` and ``window_compiles``.  A reader that finds
nothing to read returns None and the metric is left out of the line.
"""
from __future__ import annotations


def mean(values):
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def phase_s(run, *names):
    """Mean over the window's jobs of the summed seconds of the driver's
    Timers phases ``names``; None when no job has any of them."""
    return mean(sum(j["phases"].get(n, 0.0) for n in names)
                if any(n in j["phases"] for n in names) else None
                for j in run["jobs"])


def counter(job, name):
    return job["counters"].get(name, 0.0)


def per_block_ms(run, seconds_key):
    """Device seconds of the traced job under ``seconds_key`` of the trace
    reduction, per cycle block the capture holds, in ms."""
    trace = run["trace"]
    if trace is None or not trace.get("blocks"):
        return None
    return 1e3 * trace[seconds_key] / trace["blocks"]
