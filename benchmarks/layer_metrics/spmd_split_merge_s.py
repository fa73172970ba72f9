"""SPMD loop: spans ``dist split`` (mesh to host, partition, split into
shards, comm tables, the shards committed to their devices) + ``dist
merge`` (the one pull of every shard and ``merge_shards`` on the host)
per job: the two steps of the loop at whole-mesh width."""
from readers import phase_s


def read(run):
    return phase_s(run, "dist split", "dist merge")
