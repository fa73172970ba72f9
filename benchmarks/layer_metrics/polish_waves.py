"""tail: waves of the merged ``sliver_polish`` loop a job ran, counter
``tail.polish_waves``.  The loop stops on the first wave that applies no
collapse and no swap, so one of them is a no-op by construction."""
from readers import mean


def read(run):
    return mean(j["counters"].get("tail.polish_waves")
                for j in run["jobs"])
