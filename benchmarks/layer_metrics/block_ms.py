"""cycle block: dispatch-to-pull per block on the host's clock,
``groups.pipeline.compute_s`` / ``groups.dispatches``."""
from readers import counter, mean


def read(run):
    return mean(1e3 * counter(j, "groups.pipeline.compute_s")
                / counter(j, "groups.dispatches")
                for j in run["jobs"] if counter(j, "groups.dispatches"))
