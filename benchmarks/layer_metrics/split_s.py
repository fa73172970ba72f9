"""grouped outer loop: spans ``grp split`` (mesh to host, partition,
split into shards, padding) + ``grp upload`` (the stacked state and the
pass's device state committed to the chip) per job."""
from readers import phase_s


def read(run):
    return phase_s(run, "grp split", "grp upload")
