"""grouped outer loop: of the row executions a job dispatched (counter
``groups.dispatches`` times the ``groups`` of its ``grp split`` span: a
block runs every group's row), the share the device-resident quiet mask
``lax.cond``-skipped (counter ``groups.cond_skipped``: a group that
posted no operation in a swap-inclusive block is a fixed point,
parallel/sched.py).  0 % says the mask spared the device nothing: every
group worked in every block.  None where the program lacks the counter,
the job dispatched nothing, or the split span has no ``groups``."""
from readers import mean
from span_fields import last_job_spans


def read(run):
    splits = last_job_spans("grp split")
    groups = splits[0].get("groups") if splits else None

    def share(c):
        rows = c.get("groups.dispatches", 0.0) * (groups or 0)
        if not rows or c.get("groups.cond_skipped") is None:
            return None
        return 100.0 * c["groups.cond_skipped"] / rows
    return mean(share(j["counters"]) for j in run["jobs"])
