"""tail: stages the merged polish's waves skipped in a job for want of an
input, counters ``tail.collapse_skipped`` (a wave that met no tet under
the sliver threshold builds no edge table) + ``tail.exit_adj_skipped`` (a
wave whose ``swap23`` applied no swap keeps the adjacency it built for
it): of twice ``polish_waves``.  Each is a tenth of a wave's seconds or
more; 0 says every wave still had slivers and 2-3 swaps to do.  None
where the program has no such counters."""
from readers import mean


def read(run):
    return mean(j["counters"]["tail.collapse_skipped"]
                + j["counters"]["tail.exit_adj_skipped"]
                for j in run["jobs"]
                if "tail.collapse_skipped" in j["counters"]
                and "tail.exit_adj_skipped" in j["counters"])
