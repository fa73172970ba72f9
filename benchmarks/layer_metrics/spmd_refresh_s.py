"""SPMD loop: spans ``dist refresh`` per job: the session numbering
extended by the vertices an iteration made and the cross-shard surface
analysis (ridges, corners and references with the dihedrals across the
interfaces), on the devices, after every iteration's blocks."""
from readers import phase_s


def read(run):
    return phase_s(run, "dist refresh")
