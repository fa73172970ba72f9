"""The counts a span of the program carries (README, Observability: a
split's ``groups``/``capT``/``largest``, the ``run`` span's ``ne_in``).
``job.run_job`` hands a job's spans on as (name, start, end) and leaves
their fields in the program's ring, which it empties when a job starts:
after a window the ring holds the LAST job's records, and every job of a
window is staged from the same input."""
from __future__ import annotations


def last_job_spans(name: str) -> list[dict]:
    """The records of the last job's spans called ``name``, in the order
    they closed; empty on a program without such a span."""
    from parmmg_tpu.obs.trace import TRACER
    return [r for r in TRACER.ring
            if r.get("kind") == "span" and r.get("name") == name]
