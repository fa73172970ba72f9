"""SPMD loop: of the live tets the last iteration started from (counter
``dist.live_tets``), the share the interface displacement moved to
another shard over the job (``dist.migrated_tets``): how much of the
mesh the seams sweep between two iterations.  None where the program
lacks the counters or no tet was live."""
from readers import mean


def read(run):
    def share(c):
        if not c.get("dist.live_tets") or "dist.migrated_tets" not in c:
            return None
        return 100.0 * c["dist.migrated_tets"] / c["dist.live_tets"]
    return mean(share(j["counters"]) for j in run["jobs"])
