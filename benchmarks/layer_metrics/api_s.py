"""entry: seconds a job spent in the ``ParMesh`` setters and getters,
which lie outside ``run``: counters ``api.set_s`` + ``api.get_s``."""
from readers import mean


def read(run):
    def one(job):
        c = job["counters"]
        if "api.set_s" not in c and "api.get_s" not in c:
            return None
        return c.get("api.set_s", 0.0) + c.get("api.get_s", 0.0)
    return mean(one(j) for j in run["jobs"])
