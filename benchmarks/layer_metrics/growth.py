"""result: output tets over input tets, the checker's ``ntets`` of a
window's job over the ``ne_in`` its ``run`` span staged (every job of a
window is staged from the same input).  What the size map asks of the
mesh: 1.3 in ``iso-growth``, under 1 where a job coarsens, 4.9 in
``iso-refine``.  Mean over the window's jobs.  None where the span has
no ``ne_in`` or no job was checked."""
from readers import mean
from span_fields import last_job_spans


def read(run):
    runs = last_job_spans("run")
    if not runs or not runs[-1].get("ne_in"):
        return None
    return mean(j["numbers"]["ntets"] / runs[-1]["ne_in"]
                for j in run["jobs"] if j.get("numbers", {}).get("ntets"))
