"""tail: of the edge tables and adjacencies the merged polish's waves
derived in a job (counter ``tail.tables``: the collapse stage's table
where it ran, the edge swaps' and the ring swaps', ``swap23``'s
adjacency, the exit adjacency where it was rebuilt), the share taken off
the sort the last derivation left (``tail.tables_merged``): the rows the
stages dirtied since merged into it, or the sort as it is when there were
none, and not the whole mesh sorted again.  A job's first edge table and
first adjacency have nothing to merge into, so 26 tables of which 24
merged read 92.3 %; a table whose dirty rows outnumber the widest band is
sorted in full and counts against the share.  None where the program has
no such counters or a job derived no table."""
from readers import mean


def read(run):
    def share(c):
        tables, merged = c.get("tail.tables"), c.get("tail.tables_merged")
        if not tables or merged is None:
            return None
        return 100.0 * merged / tables
    return mean(share(j["counters"]) for j in run["jobs"])
