"""grouped outer loop: span ``grp displace`` (the merged mesh to the
host and ``move_interfaces`` between two passes) per job."""
from readers import phase_s


def read(run):
    return phase_s(run, "grp displace")
