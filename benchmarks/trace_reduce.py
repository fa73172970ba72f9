"""From a profiler capture (``.xplane.pb``) of ONE job to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a v5e
capture of jax 0.9.0 holds (``python3 benchmarks/trace_reduce.py <file>``
prints it): a plane ``/device:TPU:<i>`` per chip with the lines ``XLA
Modules`` (one event per executed program, ``jit_run(<id>)``) and ``XLA
Ops`` (one event per executed HLO op; its NAME is the instruction's
whole text, ``%sort.0 = (...) sort(...)``, and it carries no scope path
and no category), and a plane ``/host:CPU`` with a line per host thread:
the runtime's TraceMes and, on the Python thread, ``PjitFunction(<name>)``
and our own ``TraceAnnotation``s (``bench.job`` / ``bench.stage`` /
``bench.run`` / ``bench.pull``).

So: the opcode, the Pallas kernel's name and its shapes are parsed from
the instruction text; the ``jax.named_scope`` names (``grp_cycle*``) are
NOT in the capture, and the cycle block's device time is taken by its
program instead (module events named ``jit_run``: one ``grp_cycle0``
scope is all a block of length 1 holds besides stacking its counters).

Busy time is the union of the op intervals (a ``while`` and the ops of
its body overlap; a sum would count the body twice).  Sums by kind leave
out the control-flow ops for the same reason.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
BLOCK_MODULE = re.compile(r"^jit_run\b")    # groups._group_block_program
# `%name.3 = <shape> opcode(operands), attributes`; shapes hold no
# lower-case word followed by "("
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][a-z\-]*)\(")
CONTROL_FLOW = ("while", "conditional", "call")
TABLE_OPCODES = ("sort", "scatter", "gather")


def union_s(intervals) -> float:
    """Seconds covered by [(start_ns, end_ns), ...]."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def gaps(intervals, lo, hi):
    """The parts of [lo, hi] no interval covers, as (start, end)."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def parse_op(text: str) -> dict:
    """An op event's name -> short name, opcode and what kind of work it
    is.  ``tables``: a sort, or a gather/scatter — which this compiler
    emits as fusions of kind kCustom (a plain ``gather``/``scatter``
    opcode where it does not fuse them).  ``kernel``: (name, elements) of
    a Pallas ``tpu_custom_call``: the instruction is named after the
    ``pallas_call(name=...)``, the elements are its [rows, 128] result."""
    m = INSTRUCTION.match(text)
    name, opcode = (m.group(1), m.group(2)) if m else (text, "")
    base = re.sub(r"[.\d]+$", "", name)
    kernel = None
    if opcode == "custom-call" and "tpu_custom_call" in text:
        shape = re.search(r"\[(\d+),128\]", text)
        kernel = (base, int(shape.group(1)) * 128 if shape else None)
    return {
        "name": name, "short": base, "opcode": opcode, "kernel": kernel,
        "control": opcode in CONTROL_FLOW,
        "tables": opcode in TABLE_OPCODES
        or (opcode == "fusion" and "kind=kCustom" in text),
    }


def device_planes(profile) -> dict:
    """{plane name: {"ops": [...], "modules": [...]}} of every TPU plane;
    an op is {start, end (ns), short, opcode, kernel, control, tables}."""
    out = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    ops.append({"start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                **parse_op(ev.name)})
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    modules.append({"name": ev.name, "start": ev.start_ns,
                                    "end": ev.start_ns + ev.duration_ns})
        out[plane.name] = {"ops": ops, "modules": modules}
    return out


def host_events(profile) -> list[dict]:
    """Events of the host threads that carry our annotations (the Python
    thread: ``PjitFunction(<name>)`` says which program it was in)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            events = [{"name": ev.name, "start": ev.start_ns,
                       "end": ev.start_ns + ev.duration_ns}
                      for ev in line.events]
            if any(e["name"].startswith("bench.") for e in events):
                out += events
    return out


def name_gap(gap, hosts, spans) -> str:
    """What the host was doing in a device gap: the driver's Timer phase
    that holds its middle, and the shortest host event covering at least
    half of it."""
    s, e = gap
    mid = 0.5 * (s + e)
    phase = next((n for n, ps, pe in sorted(spans, key=lambda r: r[2] - r[1])
                  if ps <= mid <= pe), "")
    best = None
    for h in hosts:
        cover = min(e, h["end"]) - max(s, h["start"])
        if cover >= 0.5 * (e - s) and (
                best is None or h["end"] - h["start"]
                < best["end"] - best["start"]):
            best = h
    label = best["name"] if best else "nothing named"
    return f"{phase}: {label}" if phase else label


def _seconds(op) -> float:
    return (op["end"] - op["start"]) / 1e9


def reduce_profile(profile, job_spans=(), job_t_epoch=None,
                   block_module=BLOCK_MODULE) -> dict:
    """Numbers of one traced job.  ``job_spans``: the driver's Timer
    spans of that job as (name, start, end) in epoch seconds;
    ``job_t_epoch``: the epoch second the job began at (where the
    ``bench.job`` annotation opens), which ties the two clocks;
    ``block_module``: which programs are cycle blocks."""
    planes = {k: v for k, v in device_planes(profile).items() if v["ops"]}
    if not planes:
        raise ValueError("the capture holds no TPU plane with XLA ops")
    hosts = host_events(profile)
    marks = [h for h in hosts if h["name"] == "bench.job"]
    if marks:
        lo, hi = marks[0]["start"], marks[0]["end"]
    else:
        lo = min(op["start"] for p in planes.values() for op in p["ops"])
        hi = max(op["end"] for p in planes.values() for op in p["ops"])
    busy = [union_s([(op["start"], op["end"]) for op in p["ops"]])
            for p in planes.values()]
    # sums by kind over the FIRST chip's ops (one chip a cell today)
    first = next(iter(planes.values()))
    ops = first["ops"]
    leaves = [op for op in ops if not op["control"]]
    blocks = sorted((m["start"], m["end"]) for m in first["modules"]
                    if block_module.match(m["name"]))
    starts = [b[0] for b in blocks]
    in_block = []
    for op in ops:
        i = bisect.bisect_right(starts, op["start"]) - 1
        if i >= 0 and op["end"] <= blocks[i][1]:
            in_block.append((op["start"], op["end"]))
    kernels: dict = {}
    by_name: dict = {}
    for op in leaves:
        if op["kernel"]:
            rec = kernels.setdefault(op["kernel"], {
                "name": op["kernel"][0], "elements": op["kernel"][1],
                "seconds": 0.0, "calls": 0})
            rec["seconds"] += _seconds(op)
            rec["calls"] += 1
        # a kernel by its name, any other op by its instruction (fusions
        # are anonymous: "fusion.12" finds it in the program's HLO)
        key = op["short"] if op["kernel"] else op["name"]
        by_name[key] = by_name.get(key, 0.0) + _seconds(op)
    # the job's Timer spans on the trace's clock
    spans = []
    if marks and job_t_epoch is not None:
        off = marks[0]["start"] - job_t_epoch * 1e9
        spans = [(n, s * 1e9 + off, e * 1e9 + off) for n, s, e in job_spans]
    named = [h for h in hosts if h["name"] != "bench.job"
             and h["end"] > lo and h["start"] < hi]
    idle: dict = {}
    for g in gaps([(op["start"], op["end"]) for op in ops], lo, hi):
        if g[1] - g[0] < 1e6:           # under a millisecond: not a stall
            key = "gaps under 1 ms"
        else:
            key = name_gap(g, named, spans)
        idle[key] = idle.get(key, 0.0) + (g[1] - g[0]) / 1e9

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (hi - lo) / 1e9,
        "chips": len(busy),
        "blocks": len(blocks),
        "block_s": union_s(in_block),
        "sort_scatter_s": sum(_seconds(op) for op in leaves if op["tables"]),
        "pallas_s": sum(k["seconds"] for k in kernels.values()),
        "kernels": [k for k in kernels.values() if k["elements"]],
        "breakdown": {"device_ops": top(by_name), "idle_gaps": top(idle)},
    }


def reduce_dir(trace_dir: str, job: dict) -> dict:
    return reduce_profile(load(find_xplane(trace_dir)), job["spans"],
                          job["t_epoch"])


def describe(profile, limit: int = 6) -> str:
    """What a capture holds, for a reader who has not seen one: planes,
    lines, and a few events of each line with their stats."""
    rows = []
    for plane in profile.planes:
        rows.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  LINE {line.name!r}: {len(events)} events")
            step = max(1, len(events) // limit)
            for ev in events[::step][:limit]:
                rows.append(f"    {ev.name!r} start {ev.start_ns:.0f} dur "
                            f"{ev.duration_ns:.0f} stats {_stats(ev)!r}"[:700])
    return "\n".join(rows)


if __name__ == "__main__":
    import sys
    print(describe(load(sys.argv[1]),
                   int(sys.argv[2]) if len(sys.argv) > 2 else 6))
