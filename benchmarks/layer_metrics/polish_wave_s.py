"""tail: mean seconds of a ``polish wave`` span (dispatch of one merged
``sliver_polish`` to the pull of its counts), over the waves of the
window's jobs."""
from readers import mean


def read(run):
    def one(job):
        waves = sum(name == "polish wave" for name, _, _ in job["spans"])
        if not waves:
            return None
        return job["phases"]["polish wave"] / waves
    return mean(one(j) for j in run["jobs"])
