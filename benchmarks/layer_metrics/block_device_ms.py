"""cycle block: device busy time per block, the union of the device op
intervals inside the block program's module events (``jit_run``) of the
traced job, over the number of those events."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace.get("blocks"):
        return None
    return 1e3 * trace["block_s"] / trace["blocks"]
