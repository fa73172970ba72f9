"""entry: vertices whose requested size the curvature bound lowered
(``h <= sqrt(8 hausd / kappa)``, ``ops/metric.hausd_metric_bound``) while
the job built its metric, counter ``surf.bound_verts``: how much of the
size map the surface dictates.  None where the program has no such
counter."""
from readers import mean


def read(run):
    return mean(j["counters"].get("surf.bound_verts") for j in run["jobs"])
