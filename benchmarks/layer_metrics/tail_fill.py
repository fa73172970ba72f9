"""tail: live share of the rows the merged tail runs over, counters
``tail.rows_live`` / ``tail.rows_cap`` (live tets and ``capT`` of the
mesh at the entry of the merged polish).  Every sort, gather and scatter
of a polish wave and a fem round runs over the capacity, so the wave's
seconds follow this share, not the operations it applies: 33 % is a
merged mesh padded to 3x, 67 % one at 1.5x."""
from readers import mean


def read(run):
    return mean(100.0 * j["counters"]["tail.rows_live"]
                / j["counters"]["tail.rows_cap"]
                for j in run["jobs"] if j["counters"].get("tail.rows_cap"))
