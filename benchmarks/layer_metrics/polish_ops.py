"""tail: collapses + swaps the merged polish applied in a job, counter
``tail.polish_ops``; over ``polish_waves`` it is the operations a wave
buys."""
from readers import mean


def read(run):
    return mean(j["counters"].get("tail.polish_ops") for j in run["jobs"])
