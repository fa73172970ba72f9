"""entry: seconds of XLA backend compiles that set-up paid (warm-up job
included): the process total of counter ``compile.backend_s`` less what
the window's jobs added to it."""
from job import program_counters


def read(run):
    name = "compile.backend_s"
    total = program_counters().get(name)
    if total is None:
        return None
    return total - sum(j["counters"].get(name, 0.0) for j in run["jobs"])
