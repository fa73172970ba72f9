"""grouped outer loop: of the rows a job's blocks dispatched, the share
that held a group: 100 x (1 - ``groups.rows_dead`` / ``groups.rows``).
A block is ``ceil(G / R)`` dispatches of the one ``(R, capP, capT)``
program the first cut compiled, and the rows past G in the last tile
are dead (``lax.cond`` identities, counted by ``groups.rows_dead``): the
price of one block program whatever the count.  Mean over the window's
jobs that re-cut (``groups.recuts``: a job that never did runs one tile
of its own R rows).  None where none did or the program lacks the
counters."""
from readers import counter, mean


def read(run):
    return mean(100.0 * (1.0 - counter(j, "groups.rows_dead")
                         / counter(j, "groups.rows"))
                for j in run["jobs"]
                if counter(j, "groups.recuts") and counter(j, "groups.rows"))
