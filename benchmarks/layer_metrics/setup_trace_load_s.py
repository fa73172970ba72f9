"""entry: seconds set-up spent tracing and lowering programs (counter
``compile.trace_lower_s``) and loading executables from the persistent
cache (``compile.cache_load_s``): process totals less what the window's
jobs added."""
from job import program_counters


def read(run):
    names = ("compile.trace_lower_s", "compile.cache_load_s")
    totals = program_counters()
    if not any(n in totals for n in names):
        return None
    return sum(totals.get(n, 0.0)
               - sum(j["counters"].get(n, 0.0) for j in run["jobs"])
               for n in names)
