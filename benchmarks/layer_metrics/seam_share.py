"""grouped outer loop: of the vertices a job's FIRST ``grp split`` cut
(``verts``), the share that lies in two or more groups
(``seam_verts``): the first pass freezes them and only the second,
after the displacement, adapts there.  5.9 % with 2 groups of 12k tets,
15.1 % with 6 of 13.8k.  None where the program's split span carries no
such fields."""
from span_fields import last_job_spans


def read(run):
    splits = last_job_spans("grp split")
    if not splits or not splits[0].get("verts") \
            or splits[0].get("seam_verts") is None:
        return None
    return 100.0 * splits[0]["seam_verts"] / splits[0]["verts"]
