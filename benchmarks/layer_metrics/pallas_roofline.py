"""kernels: time-weighted mean over the Pallas kernels of (bytes a call
must move, kernel_bytes.py, times its calls) / (its device time x the chip's HBM
bandwidth), in %.  The memory roofline: none of them is compute-bound."""
from kernel_bytes import kernel_bytes


def read(run):
    trace = run["trace"]
    if trace is None or not trace.get("kernels"):
        return None
    least_s = sum(
        k["calls"] * kernel_bytes(k["name"], k["elements"])
        / run["peaks"]["hbm_bytes_per_s"] for k in trace["kernels"])
    spent_s = sum(k["seconds"] for k in trace["kernels"])
    return 100.0 * least_s / spent_s if spent_s else None
