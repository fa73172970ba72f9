"""tail: Timer ``fem conformity`` per job: the rounds of
``driver._finish_run`` that split the interior edges between two boundary
points, each an edge table, a split wave and an adjacency at the merged
mesh's width (host-staged in the grouped and the SPMD path), until one
finds no candidate.  Part of ``tail_s``."""
from readers import phase_s


def read(run):
    return phase_s(run, "fem conformity")
