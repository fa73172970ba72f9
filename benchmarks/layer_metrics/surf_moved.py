"""cycle block: surface vertices a job's smoothing waves moved, counter
``surf.bmoved`` (of ``adapt.nmoved``).  A regular surface vertex slides
in its tangent plane; on a curved fan it is then put back onto the
surface and kept if the step left the old one by at most ``hausd``, so a
sound ball reads thousands and 0 says the curved slide is gone.  None
where the program has no such counter."""
from readers import mean


def read(run):
    return mean(j["counters"].get("surf.bmoved") for j in run["jobs"])
