"""Device seconds by phase: the scopes of the cycle, read off the
executable that ran, joined with a profiler capture.

A capture's op events carry the instruction and its time and nothing else
(a v5e ``XLA Ops`` event is the instruction's text; the ``named_scope``
path is not in it), but the executable keeps the path: every optimised
instruction's ``metadata={op_name="jit(run)/.../cyc.split/tab.edges/..."}``
holds the scopes ``ops/adapt`` and the table makers put round their
stages (``cyc.*``, ``pol.*``, ``fem.*``; ``tab.edges``,
``tab.adjacency``).  So

:func:`scope_map`  lowers a governed entry again from the abstract
    signature the compile ledger kept when the entry lowered its program
    (``utils/compilecache``: a hit in jax's own caches, or in the
    persistent cache: no backend compile), reads the optimised text once
    and returns ``{instruction: (phase, table)}`` with the program's
    static counts;
:func:`by_phase`  sums seconds by instruction into seconds by phase;
:func:`digest`  does both for a capture: the op events inside the mapped
    programs' executions, by phase, and a row a dispatch (a ``grp
    block``, a ``polish wave``, a ``fem round`` span).

Nothing here runs unless somebody asks: ``obs.trace.profile_capture``
does when it closes a capture it started (``PARMMG_PROFILE_DIR``), and
emits ONE ``device_phases`` event; ``python3 -m parmmg_tpu.obs.devtime
<capture dir>`` prints the same table for a capture an operator kept
(the program leaves the maps it used beside the ``.xplane.pb``).

**The executable carries the metadata of whoever compiled it.**  jax
leaves op metadata out of the persistent cache's key, so a block program
taken from a cache that an older checkout wrote has that checkout's
scopes: none.  ``counts["scoped"] / counts["ops"]`` near 0 says so (the
digest logs it); the cure is a cache directory this checkout wrote.
"""
from __future__ import annotations

import bisect
import glob
import json
import math
import os
import re
import time
from typing import NamedTuple

from ..utils.compilecache import BLOCK_ENTRY, DIST_BLOCK_ENTRY, LEDGER

# the governed entries whose programs a run's capture is joined with
# (the cycle block on the device; the merged polish and the fem round
# on the host): entry -> (the span ONE dispatch of it runs under, whose
# ``prog`` says which of the entry's programs that dispatch ran; the
# field of the ``device_phases`` event its seconds go under)
ENTRIES = {BLOCK_ENTRY: ("grp block", "block"),
           DIST_BLOCK_ENTRY: ("dist block", "dist"),
           "adapt.sliver_polish": ("polish wave", "polish"),
           "adapt.fem_pass": ("fem round", "fem")}
PHASE_PREFIXES = ("cyc.", "pol.", "fem.")
TABLE_PREFIX = "tab."
UNSCOPED = "unscoped"
CONTROL_FLOW = ("while", "conditional", "call")
# opcodes that are never an op event of their own
NOT_EXECUTED = ("parameter", "constant", "get-tuple-element", "tuple",
                "bitcast", "after-all", "opt-barrier", "partition-id",
                "replica-id")
SIDECAR = "scope_map.json"
# a second backend compile of a program that took longer than this is
# not worth a map (the cycle block at real capacity: minutes, and tens
# of GB of host memory); with a persistent cache it is a load instead
COLD_COMPILE_LIMIT_S = 30.0
# a program with fewer of its instructions under a phase than this was
# compiled by a checkout that had no scopes (module docstring)
STALE_SHARE = 0.5

# `  [ROOT ]%name = <shape> opcode(operands), attributes`; shapes hold no
# lower-case word followed by "(" (benchmarks/trace_reduce.py reads a
# capture's events by the same rule)
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][a-z\-]*)\(")
_EVENT_NAME = re.compile(r"^%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"\b(?:body|condition|to_apply|calls|true_computation"
                     r"|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_MODULE_ID = re.compile(r"\(\d+\)$")
# `%name = f32[8516,3]{1,0:T(8,128)} opcode(`: an array result's type and
# dimensions (a tuple's has no such head and reads None)
_RESULT = re.compile(r" = (\w+)\[([\d,]*)\]")
_SHAPE = re.compile(r"\w+\[[\d,]*\](?:\{[^}]*\})?")


class ScopeMap(NamedTuple):
    """What :func:`scope_map` reads off one executable.

    ``phases``   instruction -> (phase, table or None), every instruction
                 outside fused computations, control flow included;
    ``counts``   ``ops`` instructions that can appear as an op event
                 (outside fused computations; ``while`` / ``conditional``
                 / ``call`` and what never executes left out), ``scoped``
                 those of them under a phase, ``sorts`` the ``sort``
                 instructions among them, ``sorts_by_phase``; where the
                 program's capacities are known, ``scalar_gathers``,
                 ``perm_gathers`` and their ``_by_phase``
                 (:func:`map_from_text`);
    ``control``  the control-flow instructions: their events span their
                 bodies', so no sum counts them;
    ``module``   the name of the program's module events in a capture.
    """
    phases: dict
    counts: dict = {}
    control: frozenset = frozenset()
    module: str = ""


# ---------------------------------------------------------------------------
# the map
# ---------------------------------------------------------------------------
def scopes_of(op_name: str) -> tuple:
    """(phase, table) of an ``op_name`` path: its first ``cyc.*`` /
    ``pol.*`` / ``fem.*`` component and its first ``tab.*`` one."""
    phase = table = None
    for part in op_name.split("/"):
        if phase is None and part.startswith(PHASE_PREFIXES):
            phase = part
        elif table is None and part.startswith(TABLE_PREFIX):
            table = part
    return phase, table


class Instruction(NamedTuple):
    name: str
    opcode: str
    op_name: str
    callees: list
    dims: tuple | None      # an array result's dimensions
    operands: list          # the operands' names


def _operands(line: str, start: int) -> list:
    """The names between the ``(`` at ``start`` and the ``)`` that closes
    it; an operand may carry its shape in front (XLA:CPU's text does)."""
    rest = _SHAPE.sub("", line[start + 1:])
    return [tok.strip().lstrip("%")
            for tok in rest[:rest.find(")")].split(",") if tok.strip()]


def parse_hlo(text: str):
    """(module name, entry computation, {computation: [Instruction]}) of
    an optimised module's text."""
    module, entry, comps, cur = "", None, {}, None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] not in " }":
            if line.startswith("HloModule"):
                module = line.split()[1].rstrip(",")
            elif line.endswith("{"):
                head = line.split()
                is_entry = head[0] == "ENTRY"
                name = head[1 if is_entry else 0].lstrip("%")
                cur = comps.setdefault(name, [])
                if is_entry:
                    entry = name
            continue
        m = _INSTRUCTION.match(line) if cur is not None else None
        if m is None:
            continue
        op = _OP_NAME.search(line)
        callees = _CALLEE.findall(line)
        for group in _BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        res = _RESULT.search(line, 0, m.end())
        dims = None if res is None else tuple(
            int(d) for d in res.group(2).split(",") if d)
        cur.append(Instruction(m.group(1), m.group(2),
                               op.group(1) if op else "", callees, dims,
                               _operands(line, m.end() - 1)))
    return module, entry, comps


def map_from_text(text: str, capP: int | None = None,
                  capT: int | None = None) -> ScopeMap:
    """The scope map of an optimised module's text.  An instruction whose
    own path names no phase takes the phase of the instruction that
    calls its computation (a ``while`` body, a ``conditional`` branch, a
    ``call``), else ``unscoped`` (the ``lax.map`` row's glue, and the
    copies the compiler puts between two stages: 3.2 % of an
    ``iso-growth`` block's device seconds, ``PERF.md`` section 5).

    With the program's capacities (``capP`` vertices, ``capT`` tets a
    mesh) the counts also hold ``scalar_gathers``: the ``gather``
    instructions, inside fused computations too, that fetch ONE scalar
    an index out of a per-vertex vector at a tet table's width or over
    (operand of rank 1 and at most ``capP + 1`` elements, result of at
    least ``capT`` elements), the dear kind of fetch on the chip
    (``ops/rowpack``; PERF.md section 5, PR 42), and ``perm_gathers``:
    those whose result has at least ``capT`` rows and whose table has
    exactly as many rows as the result, a table as long as its index
    (``x[order]``, ``x[partner]``: a fetch through a permutation, which
    has no cheap form on the chip and which a sort that carries ``x`` as
    an operand, or a shift, does without; ``ops/edges.sort_carry``, PR
    45), fusions inside fusions included (what this compiler makes of
    ``.at[order].set``); each with its ``_by_phase``."""
    module, entry, comps = parse_hlo(text)
    phases: dict = {}
    control = set()
    counts = {"ops": 0, "scoped": 0, "sorts": 0, "sorts_by_phase": {}}
    if capP is not None and capT is not None:
        counts.update(scalar_gathers=0, scalar_gathers_by_phase={},
                      perm_gathers=0, perm_gathers_by_phase={})
    dims_of = {i.name: i.dims for body in comps.values() for i in body}

    def count(key, phase):
        counts[key] += 1
        by = counts[key + "_by_phase"]
        by[phase or UNSCOPED] = by.get(phase or UNSCOPED, 0) + 1

    def scalar_gather(i) -> bool:
        table = dims_of.get(i.operands[0]) if i.operands else None
        return i.opcode == "gather" and i.dims is not None \
            and table is not None and len(table) == 1 \
            and table[0] <= capP + 1 and math.prod(i.dims) >= capT

    def perm_gather(i) -> bool:
        table = dims_of.get(i.operands[0]) if i.operands else None
        return i.opcode == "gather" and bool(i.dims) and bool(table) \
            and i.dims[0] >= capT and table[0] == i.dims[0]

    def nested(fused, depth=0) -> list:
        """``fused`` and what the fusions among them fuse: this
        compiler writes a scatter through a permutation as a sort of the
        indices and a fusion of fusions that fetches the updates through
        the sorted permutation."""
        inner = [i for f in fused if f.opcode == "fusion"
                 for c in f.callees for i in comps.get(c, ())]
        return fused + (nested(inner, depth + 1)
                        if inner and depth < 8 else [])

    def walk(comp, inherited, depth=0):
        for instr in comps.get(comp, ()):
            name, opcode, op_name, callees = instr[:4]
            phase, table = scopes_of(op_name)
            phase, table = phase or inherited[0], table or inherited[1]
            phases[name] = (phase or UNSCOPED, table)
            if opcode in CONTROL_FLOW or (opcode.endswith("-start")
                                          and callees):
                control.add(name)
                if depth < 64:
                    for c in callees:
                        walk(c, (phase, table), depth + 1)
                continue
            if opcode in NOT_EXECUTED:
                continue
            counts["ops"] += 1
            counts["scoped"] += phase is not None
            if opcode == "sort":
                count("sorts", phase)
            if "scalar_gathers" in counts:
                fused = [i for c in callees for i in comps.get(c, ())] \
                    if opcode == "fusion" else []
                for i in [instr] + fused:
                    if scalar_gather(i):
                        count("scalar_gathers", phase)
                for i in [instr] + nested(fused):
                    if perm_gather(i):
                        count("perm_gathers", phase)

    if entry is not None:
        walk(entry, (None, None))
    return ScopeMap(phases, counts, frozenset(control), module)


_MAPS: dict = {}


def scope_map(entry: str = BLOCK_ENTRY, key: tuple | None = None
              ) -> ScopeMap:
    """The scope map of the program the governed ``entry`` lowered for
    the static-shape ``key`` (its last call's by default), read from the
    executable itself.  Lowers and compiles from the signature the ledger
    kept: the program's own jit caches answer while they live (no
    lowering, no compile: the executable that RAN), the persistent cache
    after that; whatever the backend does compile is credited to no
    governed entry.  Built once a program, and only when asked.

    Raises ``LookupError`` where the entry lowered no program in this
    process, and where a second compile could cost what the first did:
    no persistent cache directory is configured and the program's own
    compile took more than :data:`COLD_COMPILE_LIMIT_S`."""
    import jax
    key, fn, (args, kwargs), device = LEDGER.signature(entry, key)
    ident = (entry, id(fn), key)
    if ident not in _MAPS:
        spent = LEDGER.compile_seconds(entry, key)
        if spent > COLD_COMPILE_LIMIT_S \
                and not jax.config.jax_compilation_cache_dir:
            raise LookupError(
                f"{entry}: no persistent compile cache is configured and "
                f"this program took {spent:.0f} s to compile: no map "
                "(set JAX_COMPILATION_CACHE_DIR)")
        # under the default device the program was lowered under: a
        # program staged on the host takes uncommitted arguments, and
        # lowered anywhere else it is another program, for another
        # backend
        with LEDGER.ungoverned(), jax.default_device(device):
            text = fn.lower(*args, **kwargs).compile().as_text()
        _MAPS[ident] = map_from_text(text, *_capacities(args))
    return _MAPS[ident]


def _capacities(args) -> tuple:
    """(capP, capT) of the mesh a governed program's signature holds, a
    row of it where the meshes are stacked; (None, None) without one."""
    import jax
    from ..core.mesh import Mesh
    for leaf in jax.tree_util.tree_leaves(
            args, is_leaf=lambda x: isinstance(x, Mesh)):
        if isinstance(leaf, Mesh):
            return leaf.vert.shape[-2], leaf.tet.shape[-2]
    return None, None


# ---------------------------------------------------------------------------
# seconds by phase
# ---------------------------------------------------------------------------
def by_phase(seconds_by_instruction: dict, smap: ScopeMap) -> dict:
    """Seconds by instruction -> ``phases`` {phase: seconds}, ``tables``
    {``tab.*``: {phase it ran in: seconds}}, ``unscoped`` (instructions
    the map puts under no phase, or does not hold) and ``total``, their
    sum.  A value may be seconds or (seconds, calls).  Control-flow
    instructions are left out: their events span their bodies' events."""
    phases: dict = {}
    tables: dict = {}
    unscoped = 0.0
    for name, value in seconds_by_instruction.items():
        if name in smap.control:
            continue
        sec = float(value[0] if isinstance(value, (tuple, list)) else value)
        phase, table = smap.phases.get(name, (UNSCOPED, None))
        if phase == UNSCOPED:
            unscoped += sec
        else:
            phases[phase] = phases.get(phase, 0.0) + sec
        if table is not None:
            row = tables.setdefault(table, {})
            row[phase] = row.get(phase, 0.0) + sec
    return {"phases": phases, "tables": tables, "unscoped": unscoped,
            "total": unscoped + sum(phases.values())}


def _union_s(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def _module_name(event_name: str) -> str:
    return _MODULE_ID.sub("", event_name)


def _program_ops(profile, span_names):
    """Every executed-op event of a capture as (start_ns, end_ns,
    instruction, module, execution), the executions (a device's module
    events) as (start, end, module), the annotations of the spans named
    in ``span_names`` as {name: [(start, end)]}, and the intervals of
    the ops a device plane held (empty on a host-only capture).  A TPU
    plane names an op by its instruction's text and says which program
    ran in its ``XLA Modules`` line; the host's XLA:CPU names it in the
    event's ``hlo_op`` / ``hlo_module`` stats and has no such line
    (execution -1)."""
    ops, runs, names, device = [], [], {}, []
    marks = {name: [] for name in span_names}
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           _module_name(ev.name))
                          for ev in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            starts = [m[0] for m in mods]
            base = len(runs)
            runs += mods
            for ev in lines["XLA Ops"].events:
                text = ev.name
                name = names.get(text)
                if name is None:
                    name = names[text] = _EVENT_NAME.match(text).group(1)
                s = ev.start_ns
                i = bisect.bisect_right(starts, s) - 1
                inside = i >= 0 and s <= mods[i][1]
                ops.append((s, s + ev.duration_ns, name,
                            mods[i][2] if inside else "",
                            base + i if inside else -1))
                device.append((s, s + ev.duration_ns))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in marks:
                        marks[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
                        continue
                    stats = dict(ev.stats)
                    if "hlo_op" in stats and "hlo_module" in stats:
                        ops.append((ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    str(stats["hlo_op"]),
                                    str(stats["hlo_module"]), -1))
    return ops, runs, {k: sorted(v) for k, v in marks.items()}, device


def _spans_of(ops, runs, marks) -> list:
    """The index of the annotation in ``marks`` each op belongs to, -1
    for none.  An op inside a device's module event goes where that
    execution overlaps most (the device's clock and the host's differ by
    tens of microseconds in a capture, so an execution may seem to begin
    before the dispatch that started it); a host op where it began."""
    starts = [m[0] for m in marks]

    def holding(s, e):
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        best, most = -1, 0.0
        for k in range(i, min(i + 2, len(marks))):
            cover = min(e, marks[k][1]) - max(s, marks[k][0])
            if cover > most:
                best, most = k, cover
        return best
    of_run = {}
    out = []
    for op in ops:
        if op[4] >= 0:
            if op[4] not in of_run:
                of_run[op[4]] = holding(*runs[op[4]][:2])
            out.append(of_run[op[4]])
        else:
            i = bisect.bisect_right(starts, op[0]) - 1
            out.append(i if i >= 0 and op[0] <= marks[i][1] else -1)
    return out


def _seconds(ops) -> dict:
    """{instruction: seconds} of (start, end, instruction, ...) events:
    the union of an instruction's own intervals (the host runs one thunk
    on several threads; a device runs one op at a time)."""
    ivals: dict = {}
    for s, e, name, *_ in ops:
        ivals.setdefault(name, []).append((s, e))
    return {name: _union_s(iv) for name, iv in ivals.items()}


class Joined(NamedTuple):
    """What a capture's module is joined with: ``span``, the name of the
    span ONE dispatch of the program runs under; ``maps``, the
    :class:`ScopeMap` s of the entry's programs as such a span's ``prog``
    indexes them (None for a program no span of the run ran);
    ``records``, the captured run's span records of that name, in
    order."""
    span: str
    maps: list
    records: list = []


def _pick(maps: list, prog):
    """The map of a span's ``prog``.  A span that does not say takes the
    entry's one map, and None where the entry lowered several: an
    instruction's name means another op in another program."""
    if isinstance(prog, int) and 0 <= prog < len(maps):
        return maps[prog]
    return maps[0] if len(maps) == 1 else None


def _unmapped(ops) -> dict:
    """What :func:`by_phase` would give for ops no map can be chosen
    for: the union of their intervals (which of them is control flow,
    spanning others, nobody can say), all of it ``unscoped``."""
    sec = _union_s(op[:2] for op in ops)
    return {"phases": {}, "tables": {}, "unscoped": sec, "total": sec}


def _add(total: dict, part: dict) -> None:
    for phase, sec in part["phases"].items():
        total["phases"][phase] = total["phases"].get(phase, 0.0) + sec
    for table, row in part["tables"].items():
        mine = total["tables"].setdefault(table, {})
        for phase, sec in row.items():
            mine[phase] = mine.get(phase, 0.0) + sec
    total["unscoped"] += part["unscoped"]
    total["total"] += part["total"]


# the fields of a span record a row carries along
_ROW_FIELDS = ("pass", "block", "wave", "prog", "active", "split",
               "collapse", "swap", "moved")


def digest(xplane_path: str, joined: dict) -> dict:
    """A capture by phase.  ``joined``: {module name as the capture has
    it (``jit_run``): :class:`Joined`}.  The k-th annotation of a span's
    name in the capture is the k-th of its records (both are on the
    profiler's clock; where their numbers differ the rows carry no span
    fields).

    ONE join: a module's op events go to the span they ran under, and a
    span's to the map of the program it says it ran (``prog``).  Returns
    ``programs`` {module: the sum of its rows, as :func:`by_phase` gives
    them, with ``events`` and ``outside``: the seconds of its ops under
    no such span, which no map can be chosen for and which count as
    ``unscoped``}, ``rows`` {module: a row a span: the span's counts,
    ``device_s``, ``phases``, ``tables``, ``unscoped``}, ``busy_s`` (the
    union of the op intervals of the device's planes; of the mapped
    programs' on a host-only capture) and ``on_device``."""
    from jax.profiler import ProfileData
    ops, runs, marks, device = _program_ops(
        ProfileData.from_file(xplane_path),
        {j.span for j in joined.values()})
    programs, rows = {}, {}
    mapped = []
    for module, j in joined.items():
        mine = [op for op in ops if op[3] == module]
        if not mine or not j.maps:
            continue
        mapped += mine
        at = marks[j.span]
        records = list(j.records) if len(j.records) == len(at) \
            else [{}] * len(at)
        parts = [[] for _ in at]
        outside = []
        for op, i in zip(mine, _spans_of(mine, runs, at)):
            (parts[i] if i >= 0 else outside).append(op)
        prog = programs[module] = dict(_unmapped(outside),
                                       events=len(mine))
        prog["outside"] = prog["total"]
        rows[module] = []
        for rec, part in zip(records, parts):
            row = {k: rec[k] for k in _ROW_FIELDS if k in rec}
            if part:
                smap = _pick(j.maps, rec.get("prog"))
                res = by_phase(_seconds(part), smap) if smap is not None \
                    else _unmapped(part)
                _add(prog, res)
                row.update(device_s=res["total"], phases=res["phases"],
                           tables=res["tables"], unscoped=res["unscoped"])
            rows[module].append(row)
    return {"programs": programs, "rows": rows,
            "busy_s": _union_s(device or [op[:2] for op in mapped]),
            "on_device": bool(device)}


# ---------------------------------------------------------------------------
# the program's own digest of a capture it made
# ---------------------------------------------------------------------------
def find_xplanes(trace_dir: str) -> list:
    return sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)


def _jsonable(joined: dict) -> dict:
    return {module: {
        "span": j.span,
        "maps": [m and {"phases": m.phases, "counts": m.counts,
                        "control": sorted(m.control), "module": m.module}
                 for m in j.maps],
        "records": [{k: v for k, v in r.items()
                     if isinstance(v, (int, float, str))}
                    for r in j.records]} for module, j in joined.items()}


def _from_json(doc: dict) -> dict:
    return {module: Joined(
        j["span"],
        [m and ScopeMap({k: tuple(v) for k, v in m["phases"].items()},
                        m["counts"], frozenset(m["control"]), m["module"])
         for m in j["maps"]], j["records"]) for module, j in doc.items()}


def digest_run(trace_dir: str) -> dict | None:
    """What ``profile_capture`` does with the capture it just closed: the
    maps of the programs this process lowered (:data:`ENTRIES`), the
    run's spans from the ring, the digest, ONE ``device_phases`` event
    under the run's ``run`` span, the table at verbosity 2, and the maps
    beside the capture for ``python3 -m parmmg_tpu.obs.devtime``.  None
    when there is nothing to join."""
    from . import trace as otrace
    t0 = time.perf_counter()
    found = find_xplanes(trace_dir)
    run = otrace.current_context().get("run")
    ring = [r for r in otrace.TRACER.ring
            if r.get("kind") == "span" and r.get("run") == run]
    joined, field_of = {}, {}
    for entry, (span, field) in ENTRIES.items():
        records = sorted((r for r in ring if r["name"] == span),
                         key=lambda r: r.get("t0", 0))
        # a map for each program a span of this run says it ran, at its
        # ``prog``; what the process lowered besides is left alone
        ran = {r.get("prog") for r in records}
        try:
            maps = [scope_map(entry, key) if i in ran else None
                    for i, key in enumerate(LEDGER.lowered_keys(entry))]
        except LookupError as e:
            otrace.log(0, f"obs: {e}", err=True)
            continue
        built = [m for m in maps if m is not None]
        if not built:
            continue
        ops = sum(m.counts["ops"] for m in built)
        scoped = sum(m.counts["scoped"] for m in built)
        if scoped < STALE_SHARE * ops:
            otrace.log(0, f"obs: {entry}: {scoped} of {ops} instructions "
                       "of the executable name a phase: it came from a "
                       "compile cache an older checkout wrote; point "
                       "JAX_COMPILATION_CACHE_DIR at a fresh directory "
                       "to read this run by phase", err=True)
        joined[built[0].module] = Joined(span, maps, records)
        field_of[built[0].module] = field
    if not found or not joined:
        return None
    root = next((r["id"] for r in reversed(ring) if r["name"] == "run"), None)
    res = digest(found[-1], joined)
    with open(os.path.join(os.path.dirname(found[-1]), SIDECAR), "w") as f:
        json.dump(_jsonable(joined), f)
    by_field = {field_of[m]: dict(prog, rows=res["rows"][m])
                for m, prog in res["programs"].items()}
    block = by_field.pop("block", {})
    otrace.event(
        "device_phases", parent=root,
        phases=block.get("phases", {}), tables=block.get("tables", {}),
        unscoped=block.get("unscoped", 0.0),
        block_s=block.get("total", 0.0), blocks=block.get("rows", []),
        busy_s=res["busy_s"], on_device=res["on_device"],
        digest_s=round(time.perf_counter() - t0, 6), **by_field)
    otrace.log(2, format_table(res), err=True)
    return res


def format_table(res: dict) -> str:
    """The digest as lines: per program the seconds of each phase with
    its ``tab.*`` part, then a row a span."""
    out = []
    for module, prog in res["programs"].items():
        out.append(f"obs: device seconds by phase, {module} "
                   f"({prog['events']} op events): total "
                   f"{prog['total']:.4f} s, unscoped "
                   f"{prog['unscoped']:.4f} s ({prog['outside']:.4f} s "
                   "under no span)")
        for phase, sec in sorted(prog["phases"].items(),
                                 key=lambda kv: -kv[1]):
            tabs = ", ".join(
                f"{t} {row[phase]:.4f}" for t, row in
                sorted(prog["tables"].items()) if phase in row)
            out.append(f"  {phase:16s} {sec:9.4f} s"
                       + (f"  ({tabs})" if tabs else ""))
        for row in res["rows"][module]:
            if "phases" not in row:
                continue
            head = " ".join(f"{k} {row[k]}" for k in (
                "pass", "block", "wave", "prog", "split", "collapse",
                "swap") if k in row)
            body = " ".join(f"{p} {1e3 * s:.1f}" for p, s in
                            sorted(row["phases"].items()))
            out.append(f"  [{head}] {1e3 * row['device_s']:.1f} ms: "
                       f"{body} unscoped {1e3 * row['unscoped']:.1f}")
    out.append(f"obs: busy {res['busy_s']:.4f} s")
    return "\n".join(out)


def main(argv=None) -> int:
    """``python3 -m parmmg_tpu.obs.devtime <capture dir>``: the table of
    every capture under the directory that the program left its maps
    beside."""
    import sys
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(main.__doc__, file=sys.stderr)
        return 2
    done = 0
    for path in find_xplanes(argv[0]):
        side = os.path.join(os.path.dirname(path), SIDECAR)
        if not os.path.exists(side):
            continue
        with open(side) as f:
            joined = _from_json(json.load(f))
        print(path)
        print(format_table(digest(path, joined)))
        done += 1
    if not done:
        print(f"no capture with a {SIDECAR} under {argv[0]}",
              file=sys.stderr)
    return 0 if done else 1


if __name__ == "__main__":
    raise SystemExit(main())
