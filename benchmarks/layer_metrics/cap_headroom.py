"""grouped outer loop: how far a job's LAST ``grp split`` stood from the
edge of its capacity, ``headroom`` = 100 x (1 - 1.25 x the fullest
group over the capacity), the lesser of tets' and vertices': a split
keeps the capacity of the pass before while its fullest group fits with
a quarter to grow (``distribute.shard_capacity``), and under 0 it takes
the next rung, a block program nobody compiled.  The displaced split of
a second pass is the fullest.  None where the program's split span
carries no such field."""
from span_fields import last_job_spans


def read(run):
    splits = last_job_spans("grp split")
    if not splits or splits[-1].get("headroom") is None:
        return None
    return float(splits[-1]["headroom"])
