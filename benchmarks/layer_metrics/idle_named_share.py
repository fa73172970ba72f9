"""device: of the seconds the device sat idle in the traced job (gaps
over 1 ms, ``trace["breakdown"]["idle_gaps"]``), the share whose host
label is an event of the program (one of its spans, a program it
called) and not the benchmark's own ``bench.*`` annotation or nothing
at all.  A key is ``<phase>: <label>`` or the label alone."""

UNDER_1_MS = "gaps under 1 ms"
UNNAMED = "nothing named"


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    gaps = [(key.rsplit(": ", 1)[-1], seconds)
            for key, seconds in trace["breakdown"]["idle_gaps"]
            if key != UNDER_1_MS]
    total = sum(seconds for _, seconds in gaps)
    if not total:
        return None
    named = sum(seconds for label, seconds in gaps
                if label != UNNAMED and not label.startswith("bench."))
    return 100.0 * named / total
