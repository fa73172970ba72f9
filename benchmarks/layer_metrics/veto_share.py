"""cycle block: of the collapses a job's waves wanted on the strength of
length or quality, applied (counter ``adapt.ncollapse``) or refused by
the ``hausd`` test (``surf.hveto``), the share refused: a map the
curvature does not bound leaves the test as the surface's only guard
(two fifths on the torus before the tensor bound), one it bounds leaves
it next to nothing to refuse; on a cube the ridges' tangent test fires.
None where the program has no such counter or a job wanted none."""
from readers import mean


def read(run):
    def share(c):
        veto, done = c.get("surf.hveto"), c.get("adapt.ncollapse", 0.0)
        if veto is None or veto + done <= 0:
            return None
        return 100.0 * veto / (veto + done)
    return mean(share(j["counters"]) for j in run["jobs"])
