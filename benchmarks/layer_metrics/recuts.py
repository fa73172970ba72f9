"""grouped outer loop: the times a job re-cut its groups because the
mesh outgrew their count, counter ``groups.recuts`` (parallel/groups.py:
a block overflowed on a group over ``-mesh-size``, or a displaced cut
would have left the block's capacity; each is more groups of the same
shape where the program used to take a bigger group, and each inside a
pass costs a merge and a split of the whole mesh: ``recut_s``).  Mean
over the window's jobs.  None where no job re-cut or the program has no
such counter."""
from readers import mean


def read(run):
    return mean(j["counters"].get("groups.recuts") for j in run["jobs"])
