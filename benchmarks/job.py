"""One whole adaptation through the program's public API.

The only file of the benchmark that touches the system under test:
``ParMesh`` staging, ``run()``, the getters, and the program's spans
(``obs.trace.TRACER.ring``) and counters (``obs.metrics.REGISTRY``).
"""
from __future__ import annotations

import time

import numpy as np


def program_counters() -> dict:
    from parmmg_tpu.obs.metrics import REGISTRY
    return dict(REGISTRY.snapshot()["counters"])


def stage(inp: dict, options: dict):
    """A fresh ParMesh holding ``inp`` (vert, tet 0-based, met) and the
    configuration's ``options`` (IParam / DParam names)."""
    from parmmg_tpu.api.params import DParam, IParam
    from parmmg_tpu.api.parmesh import ParMesh
    vert, tet, met = inp["vert"], inp["tet"], inp["met"]
    pm = ParMesh()
    pm.set_mesh_size(np_=len(vert), ne=len(tet))
    pm.set_vertices(vert)
    pm.set_tetrahedra(tet + 1)              # the API is 1-based
    if met.ndim == 1:
        pm.set_met_size(1, len(vert))
        pm.set_scalar_mets(met)
    else:
        pm.set_met_size(3, len(vert))
        pm.set_tensor_mets(met)
    for key, val in options.get("iparam", {}).items():
        pm.set_iparameter(IParam[key], val)
    for key, val in options.get("dparam", {}).items():
        pm.set_dparameter(DParam[key], val)
    return pm


def run_job(inp: dict, options: dict, annotate=None) -> dict:
    """Stage ``inp`` (vert, tet 0-based, met) into a fresh ParMesh, run,
    pull the result to the host.  Timed from the first staging call to
    the last getter's return.  ``annotate(name)`` gives a context manager
    that marks a host span on the profiler's timeline (traced job only).

    Returns the output arrays, the wall seconds, the program's phase
    seconds (its Timers' spans, by name) and the counters' increase."""
    from contextlib import nullcontext

    from parmmg_tpu.obs.trace import TRACER
    mark = annotate or (lambda name: nullcontext())
    TRACER.reset()
    before = program_counters()
    t_epoch, t0 = time.time(), time.perf_counter()
    with mark("bench.stage"):
        pm = stage(inp, options)
    with mark("bench.run"):
        rc = pm.run()
    with mark("bench.pull"):
        out_vert, _ = pm.get_vertices()
        out_tet, _ = pm.get_tetrahedra()
        out_met = pm.get_metric()
    seconds = time.perf_counter() - t0
    after = program_counters()
    spans = [r for r in TRACER.ring if r.get("kind") == "span"]
    phases: dict[str, float] = {}
    for r in spans:
        phases[r["name"]] = phases.get(r["name"], 0.0) + float(r["dur"])
    return {
        "rc": int(rc), "seconds": seconds, "t_epoch": t_epoch,
        "vert": np.asarray(out_vert), "tet": np.asarray(out_tet) - 1,
        "met": None if out_met is None else np.asarray(out_met),
        "phases": phases,
        # (name, start, end) in epoch seconds: a span's record is stamped
        # when it closes
        "spans": [(r["name"], r["ts"] - float(r["dur"]), r["ts"])
                  for r in spans if "ts" in r],
        "counters": {k: after[k] - before.get(k, 0.0) for k in after},
    }
