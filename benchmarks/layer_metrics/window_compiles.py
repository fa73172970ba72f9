"""entry: backend-compile events inside the window (run.py's own
jax.monitoring listener); a steady window has none."""


def read(run):
    return run["window_compiles"]
