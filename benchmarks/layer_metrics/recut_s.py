"""grouped outer loop: the seconds a job's re-cuts took, the ``grp
recut`` spans summed (an overflow's holds the pull, the merge, the cut,
the split and the upload of the whole mesh; the one between two passes
only the cut, because that pass's merge and the next one's split run
anyway).  Mean over the window's jobs.  None where no job has the
span."""
from readers import phase_s


def read(run):
    return phase_s(run, "grp recut")
