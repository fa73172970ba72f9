"""SPMD loop: distinct devices that held a live shard when the job
merged, counter ``dist.devices``: the cell's chips, or the job did not
run where it was told to.  None where the program lacks the counter."""
from readers import mean


def read(run):
    return mean(j["counters"]["dist.devices"] for j in run["jobs"]
                if "dist.devices" in j["counters"])
