"""grouped outer loop: how full the fullest row of the stacked block
starts, ``largest`` over ``capT`` of a job's FIRST ``grp split`` span
(the tets of the largest group the split cut, over the rows every group
of the block is padded to).  A cycle's sorts, gathers and scatters run
over the capacity, so a block's seconds follow ``capT`` and this share
says how much of them is padding: 28.5 % is two groups of 12,288 on rung
43118 (the capacity rule triples the largest group and rounds up to a
rung).  None where the program's split span carries no such fields."""
from span_fields import last_job_spans


def read(run):
    splits = last_job_spans("grp split")
    if not splits or not splits[0].get("capT") \
            or splits[0].get("largest") is None:
        return None
    return 100.0 * splits[0]["largest"] / splits[0]["capT"]
