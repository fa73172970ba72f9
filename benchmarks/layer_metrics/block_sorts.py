"""tables: the ``sort`` instructions of the cycle block program the
window's jobs ran, counted in the optimised text of the executable itself
(``parmmg_tpu.obs.devtime.scope_map``: the program lowers its block again
from the signature its compile ledger kept, which its own caches answer,
and reads the text once).  A cycle's sorts run over the whole capacity
whatever the block applies, so a PR that shares or drops a table moves
this on any machine, whatever compile cache served the program.  Read
after the window, and last of the readers: the map is built here, outside
every job and every governed entry.  None in an untraced run and in a CPU
rehearsal, and on a program without such a map."""
import sys


def read(run):
    if run["trace"] is None:
        return None
    try:
        from parmmg_tpu.obs.devtime import scope_map
        return float(scope_map().counts["sorts"])
    except (ImportError, LookupError) as e:
        # the program before PR 39 has no such module; a process that
        # lowered no block, or cannot have its map cheaply, has no map
        print(f"block program's scope map: {e!r}", file=sys.stderr)
        return None
