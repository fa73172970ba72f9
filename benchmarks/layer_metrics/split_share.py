"""cycle block: how split-led a job is: of the operations its cycle
blocks applied that change the mesh's topology (``split``, ``collapse``
and ``swap`` of its ``grp block`` spans, summed over the last job's
blocks), the share that were splits.  A job that refines (``growth``
well over 1) is split-led, one that coarsens collapse-led; the waves'
seconds follow their candidates, so the same block program is another
workload at another share.  None where the spans carry no such fields
or the job applied nothing."""
from span_fields import last_job_spans


def read(run):
    blocks = [b for b in last_job_spans("grp block")
              if b.get("split") is not None]
    ops = sum(b["split"] + b.get("collapse", 0) + b.get("swap", 0)
              for b in blocks)
    if not ops:
        return None
    return 100.0 * sum(b["split"] for b in blocks) / ops
