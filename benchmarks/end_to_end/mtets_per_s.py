"""Output tets of ALL the jobs completed in the window over the seconds
from the window's start to the last completion, /1e6, per chip."""


def read(run):
    jobs = run["jobs"]
    return (sum(j["numbers"]["ntets"] for j in jobs)
            / jobs[-1]["end_s"] / 1e6 / run["chips"])
