"""grouped outer loop: ``adaptation`` less the cycle blocks'
dispatch-to-pull seconds, per job: split, stack, merge and interface
displacement on the host."""
from readers import counter, mean


def read(run):
    return mean(j["phases"]["adaptation"]
                - counter(j, "groups.pipeline.compute_s")
                for j in run["jobs"] if "adaptation" in j["phases"])
