"""cycle block: of the indices a job's surface scatters have at full
width (counter ``surf.list_full``: 12 x ``capT`` each for the vertex
normals, the ridge tangents, the boundary tags and the smoother's surface
sums, 4 x ``capT`` for the second form, a cycle of a group that ran, the
ones a cycle may skip included), the live updates the lists those
scatters run over held (``surf.listed``): what is left of their gathers
and scatters where the program lists them, which it does where its block
is placed on a TPU.  A cube's group has 2.2k-2.6k boundary faces and a
few hundred ridge edges in 43,118 rows, so 17k of 2.24M indices a cycle
read 0.7 %.  None where the program has no such counters or ran its
scatters at full width (``surf.list_full`` 0)."""
from readers import mean


def read(run):
    def share(c):
        listed, full = c.get("surf.listed"), c.get("surf.list_full")
        if not full or listed is None:
            return None
        return 100.0 * listed / full
    return mean(share(j["counters"]) for j in run["jobs"])
