"""grouped outer loop: Timer ``adaptation`` per job over the passes it
ran (each pass folds one ``grp compute`` record into the Timers)."""
from readers import mean


def read(run):
    def one(job):
        passes = sum(name == "adaptation/grp compute"
                     for name, _, _ in job["spans"])
        if not passes or "adaptation" not in job["phases"]:
            return None
        return job["phases"]["adaptation"] / passes
    return mean(one(j) for j in run["jobs"])
