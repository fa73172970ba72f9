"""grouped outer loop: spans ``grp pull`` (the end-of-pass pull of the
stacked state) + ``grp merge`` (``merge_shards`` on the host) per job."""
from readers import phase_s


def read(run):
    return phase_s(run, "grp pull", "grp merge")
