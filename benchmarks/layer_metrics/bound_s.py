"""entry: span ``hausd bound`` per job, inside ``metric``: the fan's
curvature at every regular boundary vertex and, for tensors, the
intersection with the user's, on the host.  None where the program has
no such span."""
from readers import phase_s


def read(run):
    return phase_s(run, "hausd bound")
