"""tables: the gathers of the cycle block program the window's jobs ran
that fetch ONE scalar an index out of a per-vertex vector at a tet
table's width or over (operand of rank 1 and at most ``capP + 1``
elements, result of at least ``capT``), counted in the optimised text of
the executable itself, fused computations included
(``parmmg_tpu.obs.devtime.scope_map``, as ``block_sorts`` reads its
sorts).  The chip fetches a row of a ``[capP, k]`` table for a third of
what such a scalar costs, so a stage that packs its per-vertex columns
(``parmmg_tpu/ops/rowpack``) moves this on any machine.  Read after the
window, outside every job.  None in an untraced run and in a CPU
rehearsal, on a program without such a map, and on one whose map does not
count them (the program before PR 42)."""
import sys


def read(run):
    if run["trace"] is None:
        return None
    try:
        from parmmg_tpu.obs.devtime import scope_map
        count = scope_map().counts.get("scalar_gathers")
    except (ImportError, LookupError) as e:
        # as block_sorts: no such module, no block lowered, no cheap map
        print(f"block program's scope map: {e!r}", file=sys.stderr)
        return None
    return None if count is None else float(count)
