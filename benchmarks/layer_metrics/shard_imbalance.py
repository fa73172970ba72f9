"""SPMD loop: how far the fullest shard stands over an even cut at the
last iteration's entry, ``dist.largest_shard`` x the shards of the job's
``dist split`` span over ``dist.live_tets``, less 100 (0 % is an even
cut; the displacement moves a band of tets from shard to shard and
nothing evens it out again; lower is better).  None where the program
lacks the counters or the span its ``shards``."""
from readers import mean
from span_fields import last_job_spans


def read(run):
    splits = last_job_spans("dist split")
    shards = splits[0].get("shards") if splits else None

    def over(c):
        if not shards or not c.get("dist.live_tets") \
                or "dist.largest_shard" not in c:
            return None
        return 100.0 * c["dist.largest_shard"] * shards \
            / c["dist.live_tets"] - 100.0
    return mean(over(j["counters"]) for j in run["jobs"])
