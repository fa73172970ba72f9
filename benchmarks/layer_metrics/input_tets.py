"""entry: the tets a job staged, ``ne_in`` of its ``run`` span.  The
group count is ``ceil(ne_in / meshSize)`` and the capacity rule triples
the largest group, so this says on which side of a multiple of
``meshSize`` (2 x 16,384 = 32,768: two groups on rung 64678 under it,
three on rung 43118 over it) the cell ran.  None where the span carries
no such field."""
from span_fields import last_job_spans


def read(run):
    runs = last_job_spans("run")
    if not runs or runs[-1].get("ne_in") is None:
        return None
    return float(runs[-1]["ne_in"])
