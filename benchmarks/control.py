"""The controls of ``correct``: the program run with something it owes
the user taken away.

``one-pass``   the job stops after the first of the passes the
               configuration states (``niter`` 2): the step that would
               tempt a later PR, a faster job that met the metric worse.
               Every seed has to come out NOT correct.
``bfloat16``   the program's length and quality arithmetic carried in
               bfloat16, the precision below the float32 it computes in:
               every function that gives an edge's metric length or a
               tet's quality (the jnp formulas and the Pallas kernels'
               wrappers) gets its floating inputs and its result rounded
               through bfloat16 — what kernels that read and write
               bfloat16 would give.  Storage stays float32.  A reading,
               not a pass/fail control: PERF.md section 2 says why.

``apply(name, config)`` returns the configuration to run; call it before
the first job (a program traced earlier keeps the sound functions).
"""
from __future__ import annotations

import copy
import functools
import importlib
import sys

LOW_PRECISION_TARGETS = {
    "parmmg_tpu.ops.quality": (
        "edge_length_iso", "edge_length_ani", "quality_from_points"),
    "parmmg_tpu.ops.pallas_kernels": (
        "edge_length_iso_pallas", "edge_length_ani_pallas",
        "quality_pallas"),
}


def _through_bfloat16(x):
    import jax.numpy as jnp
    if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.asarray(x).astype(jnp.bfloat16).astype(x.dtype)
    return x


def _low(fn):
    @functools.wraps(fn)
    def low(*args, **kwargs):
        args = [_through_bfloat16(a) for a in args]
        kwargs = {k: _through_bfloat16(v) for k, v in kwargs.items()}
        return _through_bfloat16(fn(*args, **kwargs))
    return low


def low_precision() -> int:
    """Patch every loaded module of the program; returns how many
    references were replaced.  A target the program no longer has is an
    error, not a skip: a control that patches nothing would pass for a
    sound run."""
    import parmmg_tpu.api.parmesh  # noqa: F401  (loads the program)
    swapped = {}
    for modname, names in LOW_PRECISION_TARGETS.items():
        mod = importlib.import_module(modname)
        for name in names:
            fn = getattr(mod, name)
            swapped[fn] = _low(fn)
    n = 0
    for modname, mod in list(sys.modules.items()):
        if not modname.startswith("parmmg_tpu") or mod is None:
            continue
        for attr, val in list(vars(mod).items()):
            if any(val is fn for fn in swapped):
                setattr(mod, attr, swapped[val])
                n += 1
    return n


def apply(name: str, config: dict) -> dict:
    config = copy.deepcopy(config)
    if name == "one-pass":
        assert config["options"]["iparam"]["niter"] > 1
        config["options"]["iparam"]["niter"] = 1
    elif name == "bfloat16":
        print(f"control: {low_precision()} references to the length and "
              "quality functions now go through bfloat16", file=sys.stderr)
    else:
        raise ValueError(f"no control named {name!r}")
    return config
