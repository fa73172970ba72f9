"""cycle block: collapse candidates a job's waves refused because the
surface would have moved by more than ``hausd``, counter ``surf.hveto``
(cycle blocks and polish waves together).  Zero says the size map keeps
every boundary edge shorter than sqrt(8 hausd / kappa), not that the test
is gone.  None where the program has no such counter."""
from readers import mean


def read(run):
    return mean(j["counters"].get("surf.hveto") for j in run["jobs"])
