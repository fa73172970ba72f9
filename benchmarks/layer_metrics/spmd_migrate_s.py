"""SPMD loop: spans ``dist displace`` (the advancing-front flood that
relabels the tets next to an interface, and its contiguity repair) +
``dist migrate`` (the band's tets moved between shards, the interface
rebuilt, the arrivals welded, the echo check) per job: what moving the
frozen seams between two iterations costs."""
from readers import phase_s


def read(run):
    return phase_s(run, "dist displace", "dist migrate")
