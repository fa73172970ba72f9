"""tail: Timers ``bad-element polish`` + ``sequential repair`` +
``fem conformity`` per job (host-staged in the grouped path)."""
from readers import phase_s


def read(run):
    return phase_s(run, "bad-element polish", "sequential repair",
                   "fem conformity")
