"""grouped outer loop: the most groups a job's mesh was cut into, the
largest ``groups`` of its ``grp split`` spans (the count follows the
mesh: 6 at the first cut of ``iso-refine``, more after every re-cut).
Rows cost what rows cost (``block_row_ms``), so a job's block seconds
follow this count.  None where the job never re-cut (no ``grp recut``
span: its count is the ``groups`` of its first split and never moves) or
the split span has no ``groups``."""
from span_fields import last_job_spans


def read(run):
    counts = [s["groups"] for s in last_job_spans("grp split")
              if s.get("groups") is not None]
    if not counts or not last_job_spans("grp recut"):
        return None
    return float(max(counts))
