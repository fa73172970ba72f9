"""tables: the gathers of the cycle block program the window's jobs ran
that fetch through a permutation: a table with exactly as many rows as
the result, at a tet table's width or over (``x[order]`` after an
``argsort``, ``x[partner]`` for a sorted neighbour), counted in the
optimised text of the executable itself, fused computations included
(``parmmg_tpu.obs.devtime.scope_map``, as ``block_scalar_gathers`` reads
its kind).  The chip has no cheap form of such a fetch (a row out of a
table as long as its index costs what a scalar does), and a sort that
carries the column as an operand, or a shift, does without it, so a
table maker that takes what it sorted out of the sort
(``parmmg_tpu/ops/edges.sort_carry``) moves this on any machine.  Read
after the window, outside every job.  None in an untraced run and in a
CPU rehearsal, on a program without such a map, and on one whose map does
not count them (the program before PR 45)."""
import sys


def read(run):
    if run["trace"] is None:
        return None
    try:
        from parmmg_tpu.obs.devtime import scope_map
        count = scope_map().counts.get("perm_gathers")
    except (ImportError, LookupError) as e:
        # as block_sorts: no such module, no block lowered, no cheap map
        print(f"block program's scope map: {e!r}", file=sys.stderr)
        return None
    return None if count is None else float(count)
