"""cycle block: the SPMD block's dispatch-to-pull per block on the host's
clock, ``dist.pipeline.compute_s`` / ``dist.dispatches`` (one dispatch
runs every device's G rows of the block under ``shard_map`` and ends at
the pull of its psum'd counts row).  None where the program lacks the
counters or the job dispatched no SPMD block."""
from readers import counter, mean


def read(run):
    return mean(1e3 * counter(j, "dist.pipeline.compute_s")
                / counter(j, "dist.dispatches")
                for j in run["jobs"] if counter(j, "dist.dispatches"))
