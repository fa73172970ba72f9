"""result: share of the output's edges whose length in the metric lies
in the remesher's own band [1/sqrt 2, sqrt 2], over the window's jobs."""
from readers import mean


def read(run):
    return mean(j["numbers"].get("len_ok_share") for j in run["jobs"])
