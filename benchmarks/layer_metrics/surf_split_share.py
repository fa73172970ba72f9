"""cycle block: of a job's splits (counter ``adapt.nsplit``), the share
that split a boundary edge, counter ``surf.bsplit``: the midpoints the
hausd lift places on the surface.  On a cube the lift adds nothing (the
faces are flat) and the share says how much of the work is ON the
boundary; on a curved one it is the share of the splits whose point the
surface test judges.  None where the program has no such counter."""
from readers import mean


def read(run):
    return mean(100.0 * j["counters"]["surf.bsplit"]
                / j["counters"]["adapt.nsplit"]
                for j in run["jobs"] if "surf.bsplit" in j["counters"]
                and j["counters"].get("adapt.nsplit"))
