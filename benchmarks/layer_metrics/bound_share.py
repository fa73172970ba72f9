"""entry: of the regular boundary vertices the curvature bound examined
while the job built its metric (counter ``surf.bdy_verts``), the share
whose size or tensor it changed (``surf.bound_verts``;
``ops/metric.hausd_metric_bound``): how much of the surface's size map
the curvature dictates, 0 where the user's map is finer everywhere.
None where the program has no such counters or examined no vertex."""
from readers import mean


def read(run):
    return mean(100.0 * j["counters"]["surf.bound_verts"]
                / j["counters"]["surf.bdy_verts"]
                for j in run["jobs"] if "surf.bound_verts" in j["counters"]
                and j["counters"].get("surf.bdy_verts"))
