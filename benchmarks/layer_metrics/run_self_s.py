"""entry: host time of a job under no phase: the ``run`` span's seconds
less the union of the other spans inside it (every descendant lies in
one of its children, so that union is its children's)."""
from readers import mean


def read(run):
    def one(job):
        root = [(s, e) for name, s, e in job["spans"] if name == "run"]
        if not root:
            return None
        lo, hi = root[0]
        covered, end = 0.0, lo
        for s, e in sorted((max(s, lo), min(e, hi))
                           for name, s, e in job["spans"]
                           if name != "run" and e > lo and s < hi):
            covered += max(0.0, e - max(s, end))
            end = max(end, e)
        return (hi - lo) - covered
    return mean(one(j) for j in run["jobs"])
