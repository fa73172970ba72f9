"""grouped outer loop: the vertices of a job's FIRST ``grp split`` that
lie in three or more groups (``junction_verts``): where seams meet, a
vertex is frozen by several groups at once and ``merge_shards`` joins it
from that many rows.  0 with 2 groups.  None where the program's split
span carries no such field."""
from span_fields import last_job_spans


def read(run):
    splits = last_job_spans("grp split")
    if not splits or splits[0].get("junction_verts") is None:
        return None
    return float(splits[0]["junction_verts"])
