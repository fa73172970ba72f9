"""cycle block: dispatch-to-pull per ROW of a block on the host's clock,
``groups.pipeline.compute_s`` over ``groups.rows`` (the rows of the
stacks a job's blocks ran: a block runs every group's row, so 24 blocks
of 6 rows are 144).  ``block_ms`` over the rows of its block: what a
block of two, three and six rows can be compared by ("rows cost what
rows cost", PERF.md section 6).  None on a program without the
counter."""
from readers import counter, mean


def read(run):
    return mean(1e3 * counter(j, "groups.pipeline.compute_s")
                / counter(j, "groups.rows")
                for j in run["jobs"] if counter(j, "groups.rows"))
