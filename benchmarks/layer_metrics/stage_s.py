"""entry: the driver's Timers ``analysis`` + ``metric`` per job (host)."""
from readers import phase_s


def read(run):
    return phase_s(run, "analysis", "metric")
