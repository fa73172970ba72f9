"""tail: of the candidate rows the merged polish's ring and edge swap
kernels selected in a job (counter ``tail.candidate_rows``: what their
top-K let through, wave by wave), the share whose shell changed since the
kernel last looked (``tail.worklist_rows``): what its candidate stage ran
over, before rounding up to chunks.  Wave 0 judges everything; 100 % says
the list spared the waves nothing, 40 % that three fifths of a job's
candidates were known refusals.  None where the program has no such
counters or a job selected no candidate."""
from readers import mean


def read(run):
    def share(c):
        rows, listed = c.get("tail.candidate_rows"), c.get(
            "tail.worklist_rows")
        if not rows or listed is None:
            return None
        return 100.0 * listed / rows
    return mean(share(j["counters"]) for j in run["jobs"])
