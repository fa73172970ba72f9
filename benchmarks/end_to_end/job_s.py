"""Median wall seconds of the whole adaptations completed in the window
(host clock; a job takes tens of seconds)."""
import statistics


def read(run):
    return statistics.median(j["seconds"] for j in run["jobs"])
