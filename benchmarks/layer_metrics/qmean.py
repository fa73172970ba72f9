"""result: the checker's mean tet quality over the window's jobs."""
from readers import mean


def read(run):
    return mean(j["numbers"].get("qmean") for j in run["jobs"])
