"""SPMD loop: MB a job that crossed between shards, counter
``dist.exchange_bytes``: the halo exchanges of the interface echo checks
and of the analysis refresh (bytes a valid interface slot), the tets the
band migration shipped with their vertices' rows, and the band tables
the hosts exchanged (``mh.band_exchange_bytes``).  None where the
program lacks the counter."""
from readers import mean


def read(run):
    return mean(j["counters"]["dist.exchange_bytes"] / 1e6
                for j in run["jobs"]
                if "dist.exchange_bytes" in j["counters"])
