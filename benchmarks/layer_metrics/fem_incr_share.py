"""tail: of the edge tables and adjacencies the fem rounds derived in a
job with the merged polish's retained sorts handed on (counter
``tail.fem_tables``: two a round), the share taken off those sorts
(``tail.fem_tables_merged``): the rows changed since merged into them, or
the sort as it is where there were none, and not the whole mesh sorted
again.  Every round's tables have something to merge into, the first
round's too (the polish left it), so a job reads 100 %; a round after a
regrow (the state is dropped with the old capacity) sorts both in full
and counts against the share, as does a table whose changed rows
outnumber the widest band.  None where the program has no such counters
or a job's rounds derived no table off a state (``-nofem``, the
whole-mesh path)."""
from readers import mean


def read(run):
    def share(c):
        tables = c.get("tail.fem_tables")
        merged = c.get("tail.fem_tables_merged")
        if not tables or merged is None:
            return None
        return 100.0 * merged / tables
    return mean(share(j["counters"]) for j in run["jobs"])
