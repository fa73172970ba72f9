"""Process start to window start: imports, the input, cache load or
compile, the warm-up job (host clock)."""


def read(run):
    return run["setup_s"]
