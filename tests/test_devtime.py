"""Device seconds by phase (``obs/devtime``, PR 39): the scopes are in
the lowered programs, the map is read off the executable that ran and
costs nothing unless asked, ``by_phase`` adds up, and the digest of a
recorded v5e capture gives the events' own durations back."""
import hashlib
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parmmg_tpu.obs import devtime
from parmmg_tpu.obs.devtime import ScopeMap, UNSCOPED
from parmmg_tpu.obs.metrics import REGISTRY
from parmmg_tpu.utils.compilecache import BLOCK_ENTRY, LEDGER

CYC = ("cyc.table", "cyc.normals", "cyc.split", "cyc.collapse",
       "cyc.bdytags", "cyc.swap_edges", "cyc.swap23", "cyc.smooth",
       "cyc.adjacency")
POL = ("pol.collapse", "pol.swap_edges", "pol.swapgen", "pol.swap23",
       "pol.smooth", "pol.list", "pol.adjacency")
FEM = ("fem.split", "fem.bdytags", "fem.adjacency")
TAB = ("tab.edges", "tab.adjacency")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPTURE = os.path.join(ROOT, "benchmarks", "tests", "data",
                       "span_trace.xplane.pb")


# ---------------------------------------------------------------------------
# the scopes are in the programs
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small():
    """Two groups of ``cube_mesh(2)`` and the arguments of a block."""
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.distribute import split_to_shards
    from parmmg_tpu.utils.fixtures import cube_mesh
    vert, tet = cube_mesh(2)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, 0.3, m.vert.dtype)
    part = (vert[tet].mean(axis=1)[:, 0] > 0.5).astype(np.int32)
    stacked, met_s = split_to_shards(m, met, part, 2)
    args = (stacked, met_s, jnp.asarray(0, jnp.int32), jnp.ones(2, bool),
            jnp.asarray(True), jnp.asarray(True))
    return {"mesh": m, "met": met, "block_args": args}


@pytest.fixture(scope="module")
def lowered(small):
    """The lowered text, with and without debug info, of the three
    programs that carry scopes."""
    from parmmg_tpu.ops import adapt
    from parmmg_tpu.ops.topo_incr import topo_init
    from parmmg_tpu.ops.worklist import all_dirty
    from parmmg_tpu.parallel import groups
    m, met = small["mesh"], small["met"]
    block = groups._group_block_program(False, False, 0.01).__wrapped__
    low = {
        "block": block.lower(*small["block_args"]),
        "polish": adapt.sliver_polish.__wrapped__.lower(
            m, met, jnp.asarray(1000, jnp.int32), hausd=0.01, budget=64,
            worklist=all_dirty(m), topo=topo_init(m.capT)),
        "fem": adapt.fem_pass.__wrapped__.lower(m, met),
    }
    return {k: (v.as_text(), v.as_text(debug_info=True))
            for k, v in low.items()}


@pytest.mark.parametrize("program,name", [
    *(("block", n) for n in CYC + TAB),
    *(("polish", n) for n in POL + TAB),
    *(("fem", n) for n in FEM + TAB),
])
def test_every_stage_has_its_scope_in_the_lowered_program(lowered, program,
                                                          name):
    plain, debug = lowered[program]
    assert name in debug
    # a scope is metadata: without debug info the text holds none of it
    assert name not in plain


def test_the_one_scope_round_the_whole_cycle_is_gone(lowered):
    gone = "grp_" + "cycle0"
    assert all(gone not in debug for _, debug in lowered.values())


# ---------------------------------------------------------------------------
# the map: rules, on a hand-made module
# ---------------------------------------------------------------------------
HLO = '''HloModule jit_run, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(run)/while/body/cyc.collapse/mul"}
}

%compare.1 (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0), metadata={op_name="sort"}
  %b = f32[] parameter(1), metadata={op_name="sort"}
  ROOT %lt.9 = pred[] compare(%a, %b), direction=LT
}

%branch_a (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %sort.7 = f32[8]{0} sort(%q), dimensions={0}, to_apply=%compare.1, metadata={op_name="sort"}
  ROOT %copy.3 = f32[8]{0} copy(%sort.7)
}

%branch_b (r: f32[8]) -> f32[8] {
  ROOT %r = f32[8]{0} parameter(0)
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[8]{0} get-tuple-element(%t), index=1
  %slice.1 = f32[8]{0} dynamic-slice(%x, %i), metadata={op_name="jit(run)/while/body/dynamic_slice"}
  %sort.2 = f32[8]{0} sort(%slice.1), dimensions={0}, to_apply=%compare.1, metadata={op_name="jit(run)/while/body/cyc.table/tab.edges/jit(sort)/sort"}
  %copy.1 = f32[8]{0} copy(%sort.2)
  %fusion.4 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1
  %conditional.5 = f32[8]{0} conditional(%i, %fusion.4, %fusion.4), branch_computations={%branch_a, %branch_b}, metadata={op_name="jit(run)/while/body/cyc.swap23/tab.adjacency/cond"}
  ROOT %tuple.6 = (s32[], f32[8]{0}) tuple(%i, %conditional.5)
}

%cond (t: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%t.1), index=0
  ROOT %lt.1 = pred[] compare(%i.1, %i.1), direction=LT, metadata={op_name="jit(run)/while/cond/lt"}
}

ENTRY %main.9 (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %arg)
  %while.8 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(run)/while"}
  ROOT %out = f32[8]{0} get-tuple-element(%while.8), index=1
}
'''


@pytest.fixture(scope="module")
def handmade():
    return devtime.map_from_text(HLO)


def test_a_path_gives_its_first_phase_and_its_table(handmade):
    assert handmade.module == "jit_run"
    assert handmade.phases["sort.2"] == ("cyc.table", "tab.edges")
    assert devtime.scopes_of("jit(run)/pol.swapgen/tab.edges/x/cyc.split") \
        == ("pol.swapgen", "tab.edges")
    assert devtime.scopes_of("jit(run)/while/body/add") == (None, None)


def test_a_bare_op_name_takes_its_callers_phase(handmade):
    """``%sort.7`` keeps a bare ``op_name="sort"`` (a backend's clone)
    inside a branch of a conditional under ``cyc.swap23``."""
    assert handmade.phases["sort.7"] == ("cyc.swap23", "tab.adjacency")
    assert handmade.phases["copy.3"][0] == "cyc.swap23"


def test_what_names_no_phase_and_has_no_caller_that_does_is_unscoped(
        handmade):
    """One rule, the issue's: own path, else the caller's, else
    ``unscoped``: a fusion whose own line carries no path and the bare
    copy the compiler put before it are nobody's (on the chip: 3.2 % of
    an ``iso-growth`` block's device seconds, ``PERF.md`` section 5)."""
    assert handmade.phases["fusion.4"] == (UNSCOPED, None)
    assert handmade.phases["copy.1"] == (UNSCOPED, None)


def test_glue_outside_every_scope_is_unscoped(handmade):
    assert handmade.phases["slice.1"] == (UNSCOPED, None)
    assert handmade.phases["lt.1"] == (UNSCOPED, None)


def test_static_counts_leave_out_control_flow_and_what_never_runs(handmade):
    assert handmade.control == {"while.8", "conditional.5"}
    # slice.1, sort.2, copy.1, fusion.4 | lt.1 | sort.7, copy.3: not the
    # parameters, tuples and constants, nor a comparator's instructions
    assert handmade.counts["ops"] == 7
    assert handmade.counts["scoped"] == 3       # sort.2 | sort.7, copy.3
    assert handmade.counts["sorts"] == 2
    assert handmade.counts["sorts_by_phase"] == {"cyc.table": 1,
                                                 "cyc.swap23": 1}
    assert "lt.9" not in handmade.phases and "mul.1" not in handmade.phases


# a block's gathers as the TPU's compiler writes them (``kind=kCustom``
# fusions of a table and an index vector) and as XLA:CPU does (bare, the
# result with a trailing 1), at ``capP`` 8516, ``capT`` 43118
GATHERS = '''HloModule jit_run, is_scheduled=true

%fused_computation.1 (param_0.1: u32[8516], param_1.1: s32[258708,1]) -> u32[258708] {
  %param_0.1 = u32[8516]{0:T(1024)} parameter(0)
  %param_1.1 = s32[258708,1]{0,1:T(1024)} parameter(1)
  ROOT %gather.1 = u32[258708]{0:T(1024)} gather(%param_0.1, %param_1.1), offset_dims={}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}
}

%fused_computation.2 (param_0.2: f32[8516,4], param_1.2: s32[258708,1]) -> f32[258708,4] {
  %param_0.2 = f32[8516,4]{1,0:T(8,128)} parameter(0)
  %param_1.2 = s32[258708,1]{0,1:T(1024)} parameter(1)
  ROOT %gather.2 = f32[258708,4]{1,0:T(8,128)} gather(%param_0.2, %param_1.2), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,4}
}

%fused_computation.3 (param_0.3: f32[8517], param_1.3: s32[5389,1]) -> f32[5389] {
  %param_0.3 = f32[8517]{0:T(1024)} parameter(0)
  %param_1.3 = s32[5389,1]{0,1:T(1024)} parameter(1)
  ROOT %gather.3 = f32[5389]{0:T(1024)} gather(%param_0.3, %param_1.3), offset_dims={}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}
}

%fused_computation.4 (param_0.4: s32[258708], param_1.4: s32[258708,1]) -> s32[258708] {
  %param_0.4 = s32[258708]{0:T(1024)} parameter(0)
  %param_1.4 = s32[258708,1]{0,1:T(1024)} parameter(1)
  ROOT %gather.4 = s32[258708]{0:T(1024)} gather(%param_0.4, %param_1.4), offset_dims={}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}
}

ENTRY %main.9 (tag: u32[8516], xyzh: f32[8516,4], score: f32[8517], perm: s32[258708], flag: pred[8517], ends: s32[258708,1], top: s32[5389,1], tets: s32[43118,1]) -> (u32[258708], f32[258708,4], f32[5389], s32[258708], pred[43118,1], s32[43118]) {
  %tag = u32[8516]{0} parameter(0)
  %xyzh = f32[8516,4]{1,0} parameter(1)
  %score = f32[8517]{0} parameter(2)
  %perm = s32[258708]{0} parameter(3)
  %flag = pred[8517]{0} parameter(4)
  %ends = s32[258708,1]{1,0} parameter(5)
  %top = s32[5389,1]{1,0} parameter(6)
  %tets = s32[43118,1]{1,0} parameter(7)
  %fusion.1 = u32[258708]{0} fusion(%tag, %ends), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(run)/while/body/cyc.collapse/gather"}
  %fusion.2 = f32[258708,4]{1,0} fusion(%xyzh, %ends), kind=kCustom, calls=%fused_computation.2, metadata={op_name="jit(run)/while/body/cyc.collapse/gather"}
  %fusion.3 = f32[5389]{0} fusion(%score, %top), kind=kCustom, calls=%fused_computation.3, metadata={op_name="jit(run)/while/body/cyc.collapse/gather"}
  %fusion.4 = s32[258708]{0} fusion(%perm, %ends), kind=kCustom, calls=%fused_computation.4, metadata={op_name="jit(run)/while/body/cyc.table/gather"}
  %gather.5 = pred[43118,1]{1,0} gather(pred[8517]{0} %flag, s32[43118,1]{1,0} %tets), offset_dims={1}, collapsed_slice_dims={}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}, metadata={op_name="jit(run)/while/body/cyc.smooth/gather"}
  %gather.6 = s32[43118]{0} gather(s32[258708]{0} %perm, s32[43118,1]{1,0} %tets), offset_dims={}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}
  ROOT %out = (u32[258708]{0}, f32[258708,4]{1,0}, f32[5389]{0}, s32[258708]{0}, pred[43118,1]{1,0}, s32[43118]{0}) tuple(%fusion.1, %fusion.2, %fusion.3, %fusion.4, %gather.5, %gather.6)
}
'''


def test_scalar_gathers_are_the_per_vertex_vectors_at_a_tet_tables_width():
    """A rank-1 operand of at most ``capP + 1`` elements and a result of
    at least ``capT`` count, inside a fused computation (under its
    fusion's phase) and bare (XLA:CPU's, the operands' shapes in front);
    a row out of ``[capP, 4]``, a K-wide result and a table as long as
    its index do not."""
    counts = devtime.map_from_text(GATHERS, capP=8516, capT=43118).counts
    assert counts["scalar_gathers"] == 2
    assert counts["scalar_gathers_by_phase"] == {"cyc.collapse": 1,
                                                 "cyc.smooth": 1}
    assert counts["ops"] == 6 and counts["sorts"] == 0
    # the bounds are the capacities': a vertex fewer and the flag vector
    # (8517, with its drop row) is no per-vertex table, two fewer and the
    # tag vector is none; a tet more and the smoother's result is not wide
    for capP, capT, left in ((8515, 43118, 1), (8514, 43118, 0),
                             (8516, 43119, 1), (8516, 258709, 0)):
        assert devtime.map_from_text(GATHERS, capP, capT).counts[
            "scalar_gathers"] == left


def test_without_the_capacities_nothing_is_counted_as_a_scalar_gather(
        handmade):
    assert "scalar_gathers" not in devtime.map_from_text(GATHERS).counts
    assert "scalar_gathers" not in handmade.counts


# a fetch through a permutation, as the TPU's compiler writes it (a fusion
# of a table and an index vector as long as the table) and as XLA:CPU does
PERMS = '''HloModule jit_run, is_scheduled=true

%fused_computation.1 (param_0.1: s32[258708,2], param_1.1: s32[258708,1]) -> s32[258708,2] {
  %param_0.1 = s32[258708,2]{1,0:T(8,128)} parameter(0)
  %param_1.1 = s32[258708,1]{0,1:T(1024)} parameter(1)
  ROOT %gather.1 = s32[258708,2]{1,0:T(8,128)} gather(%param_0.1, %param_1.1), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,2}
}

%fused_computation.2 (param_0.2: f32[8516,4], param_1.2: s32[258708,1]) -> f32[258708,4] {
  %param_0.2 = f32[8516,4]{1,0:T(8,128)} parameter(0)
  %param_1.2 = s32[258708,1]{0,1:T(1024)} parameter(1)
  ROOT %gather.2 = f32[258708,4]{1,0:T(8,128)} gather(%param_0.2, %param_1.2), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,4}
}

ENTRY %main.5 (keys: s32[258708,2], xyzh: f32[8516,4], order: s32[258708,1], t: s32[172472], partner: s32[172472,1], top: s32[5389], rank: s32[5389,1]) -> (s32[258708,2], f32[258708,4], s32[172472], s32[5389]) {
  %keys = s32[258708,2]{1,0} parameter(0)
  %xyzh = f32[8516,4]{1,0} parameter(1)
  %order = s32[258708,1]{1,0} parameter(2)
  %t = s32[172472]{0} parameter(3)
  %partner = s32[172472,1]{1,0} parameter(4)
  %top = s32[5389]{0} parameter(5)
  %rank = s32[5389,1]{1,0} parameter(6)
  %fusion.1 = s32[258708,2]{1,0} fusion(%keys, %order), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(run)/while/body/cyc.table/tab.edges/gather"}
  %fusion.2 = f32[258708,4]{1,0} fusion(%xyzh, %order), kind=kCustom, calls=%fused_computation.2, metadata={op_name="jit(run)/while/body/cyc.table/gather"}
  %gather.3 = s32[172472]{0} gather(s32[172472]{0} %t, s32[172472,1]{1,0} %partner), offset_dims={}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}, metadata={op_name="jit(run)/while/body/cyc.adjacency/tab.adjacency/gather"}
  %gather.4 = s32[5389]{0} gather(s32[5389]{0} %top, s32[5389,1]{1,0} %rank), offset_dims={}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1}, metadata={op_name="jit(run)/while/body/cyc.swap23/gather"}
  ROOT %out = (s32[258708,2]{1,0}, f32[258708,4]{1,0}, s32[172472]{0}, s32[5389]{0}) tuple(%fusion.1, %fusion.2, %gather.3, %gather.4)
}
'''


def test_perm_gathers_are_the_tables_as_long_as_their_index():
    """A fetch through a permutation counts: a table with as many rows
    as the result, at a tet table's width or over, fused or bare, rows
    of one word or of two.  A row out of ``[capP, 4]`` does not (the
    cheap kind), nor a K-wide dedup's fetch."""
    counts = devtime.map_from_text(PERMS, capP=8516, capT=43118).counts
    assert counts["perm_gathers"] == 2
    assert counts["perm_gathers_by_phase"] == {"cyc.table": 1,
                                               "cyc.adjacency": 1}
    assert counts["scalar_gathers"] == 0 and counts["ops"] == 4
    # the other text's one: ``perm[ends]``, both 258,708 long; its
    # ``perm[tets]`` is a table longer than its index
    counts = devtime.map_from_text(GATHERS, capP=8516, capT=43118).counts
    assert counts["perm_gathers_by_phase"] == {"cyc.table": 1}
    for capT, left in ((5389, 3), (172472, 2), (172473, 1), (258709, 0)):
        assert devtime.map_from_text(PERMS, 8516, capT).counts[
            "perm_gathers"] == left


# ``.at[order].set(rows)`` as the TPU's compiler writes it: a sort of the
# indices, then a fusion of a fusion that fetches the rows through them
NESTED = '''HloModule jit_run, is_scheduled=true

%fused_computation.7.clone (param_0.7: s32[258708,2], param_1.7: s32[258708]) -> s32[258708,2] {
  %param_0.7 = s32[258708,2]{1,0:T(8,128)} parameter(0)
  %param_1.7 = s32[258708]{0:T(1024)} parameter(1)
  ROOT %gather.7 = s32[258708,2]{1,0:T(8,128)} gather(%param_0.7, %param_1.7), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,2}
}

%fused_computation.70 (param_0.70: s32[258708,2], param_1.70: s32[258708]) -> s32[258708,2] {
  %param_0.70 = s32[258708,2]{1,0:T(8,128)} parameter(0)
  %param_1.70 = s32[258708]{0:T(1024)} parameter(1)
  ROOT %fusion.7 = s32[258708,2]{1,0:T(8,128)} fusion(%param_0.70, %param_1.70), kind=kLoop, calls=%fused_computation.7.clone
}

ENTRY %main.3 (rows: s32[258708,2], sorted: s32[258708]) -> s32[258708,2] {
  %rows = s32[258708,2]{1,0} parameter(0)
  %sorted = s32[258708]{0} parameter(1)
  ROOT %fusion.70 = s32[258708,2]{1,0} fusion(%rows, %sorted), kind=kCustom, calls=%fused_computation.70, metadata={op_name="jit(run)/while/body/cyc.table/tab.edges/scatter"}
}
'''


def test_a_permutation_scatters_fetch_inside_nested_fusions_counts():
    counts = devtime.map_from_text(NESTED, capP=8516, capT=43118).counts
    assert counts["perm_gathers_by_phase"] == {"cyc.table": 1}
    assert counts["ops"] == 1 and counts["scalar_gathers"] == 0


def test_without_the_capacities_nothing_is_counted_as_a_perm_gather():
    assert "perm_gathers" not in devtime.map_from_text(PERMS).counts


# ---------------------------------------------------------------------------
# by_phase
# ---------------------------------------------------------------------------
TABLE = ScopeMap(
    phases={"fusion.1": ("cyc.split", None),
            "sort.2": ("cyc.table", "tab.edges"),
            "sort.3": ("cyc.swap_edges", "tab.edges"),
            "fusion.4": ("cyc.adjacency", "tab.adjacency"),
            "copy.5": (UNSCOPED, None),
            "while.6": (UNSCOPED, None),
            "conditional.7": ("cyc.split", None)},
    control=frozenset({"while.6", "conditional.7"}))
SECONDS = {"fusion.1": 0.25, "sort.2": 0.5, "sort.3": 0.125,
           "fusion.4": 1.0, "copy.5": 0.0625, "custom-call.9": 0.03125,
           "while.6": 2.0, "conditional.7": 0.375}


def test_by_phase_adds_up_to_its_input():
    res = devtime.by_phase(SECONDS, TABLE)
    leaves = sum(v for k, v in SECONDS.items() if k not in TABLE.control)
    assert res["total"] == leaves == 1.96875
    assert sum(res["phases"].values()) + res["unscoped"] == res["total"]
    assert res["phases"] == {"cyc.split": 0.25, "cyc.table": 0.5,
                             "cyc.swap_edges": 0.125, "cyc.adjacency": 1.0}


def test_by_phase_does_not_count_control_flow_twice():
    """A ``while``'s event spans its body's events, a ``conditional``'s
    its branch's: neither is in a sum, whatever phase it sits under."""
    res = devtime.by_phase(SECONDS, TABLE)
    assert res["phases"]["cyc.split"] == 0.25       # not 0.25 + 0.375
    assert res["total"] < SECONDS["while.6"]


def test_by_phase_puts_the_unnamed_under_unscoped():
    res = devtime.by_phase(SECONDS, TABLE)
    # copy.5 by the map, custom-call.9 because the map does not hold it
    assert res["unscoped"] == 0.0625 + 0.03125


def test_by_phase_gives_a_table_by_the_phase_it_ran_in():
    res = devtime.by_phase(SECONDS, TABLE)
    assert res["tables"] == {
        "tab.edges": {"cyc.table": 0.5, "cyc.swap_edges": 0.125},
        "tab.adjacency": {"cyc.adjacency": 1.0}}
    # seconds with their calls, as a reader may hand them over
    pairs = {k: (v, 3) for k, v in SECONDS.items()}
    assert devtime.by_phase(pairs, TABLE) == res


# ---------------------------------------------------------------------------
# digest, on a recorded v5e capture
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def capture():
    """The op events of the recorded capture inside its ``jit_block``
    module events, read here by hand."""
    from jax.profiler import ProfileData
    plane = next(p for p in ProfileData.from_file(CAPTURE).planes
                 if p.name == "/device:TPU:0")
    lines = {line.name: list(line.events) for line in plane.lines}
    mods = [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in lines["XLA Modules"]]
    ops = {}
    for ev in lines["XLA Ops"]:
        assert any(s <= ev.start_ns <= e for s, e in mods)
        name = ev.name.split(" = ")[0].lstrip("%")
        ops.setdefault(name, []).append(ev.duration_ns / 1e9)
    return {"ops": ops, "modules": len(mods),
            "busy": sum(map(sum, ops.values()))}


def _joined(*maps, records=()):
    return {"jit_block": devtime.Joined("grp block", list(maps),
                                        list(records))}


def test_digest_gives_the_events_own_durations_per_module(capture):
    names = sorted(capture["ops"])
    phases = {n: (("cyc.split", "cyc.smooth")[i % 2],
                  "tab.edges" if n.startswith("sort") else None)
              for i, n in enumerate(names)}
    res = devtime.digest(CAPTURE, _joined(ScopeMap(phases)))
    prog = res["programs"]["jit_block"]
    assert res["on_device"] and prog["events"] == sum(
        map(len, capture["ops"].values()))
    assert prog["total"] == pytest.approx(capture["busy"], rel=1e-9)
    assert prog["unscoped"] == prog["outside"] == 0.0
    for k, phase in enumerate(("cyc.split", "cyc.smooth")):
        assert prog["phases"][phase] == pytest.approx(
            sum(sum(capture["ops"][n]) for n in names[k::2]), rel=1e-9)
    # the device ran one op at a time: the union is the sum
    assert res["busy_s"] == pytest.approx(capture["busy"], rel=1e-6)
    # the capture holds two ``grp block`` annotations, a module event
    # in each: a row a block, and the program IS the sum of its rows
    blocks = res["rows"]["jit_block"]
    assert len(blocks) == capture["modules"] == 2
    assert sum(r["device_s"] for r in blocks) == pytest.approx(
        prog["total"], rel=1e-9)
    assert all(set(r["phases"]) == {"cyc.split", "cyc.smooth"}
               and "block" not in r for r in blocks)
    # with the run's span records, matched in order, a row says which
    spans = [{"block": 0, "prog": 0, "split": 7}, {"block": 1, "prog": 0}]
    rows = devtime.digest(CAPTURE, _joined(ScopeMap(phases), records=spans)
                          )["rows"]["jit_block"]
    assert [(r["block"], r["prog"]) for r in rows] == [(0, 0), (1, 0)]
    assert rows[0]["split"] == 7
    assert [r["device_s"] for r in rows] == [r["device_s"] for r in blocks]


def test_digest_with_a_map_that_lacks_an_op_says_unscoped(capture):
    names = sorted(capture["ops"])
    known = {n: ("cyc.split", None) for n in names[1:]}
    res = devtime.digest(CAPTURE, _joined(ScopeMap(known)))
    prog = res["programs"]["jit_block"]
    assert prog["unscoped"] == pytest.approx(sum(capture["ops"][names[0]]))
    assert prog["outside"] == 0.0
    assert prog["total"] == pytest.approx(capture["busy"], rel=1e-9)
    # a module the maps do not name is no program of ours
    assert devtime.digest(CAPTURE, {"jit_run": devtime.Joined(
        "grp block", [ScopeMap({})])})["programs"] == {}


def test_a_block_is_joined_with_the_map_of_the_program_it_ran(capture):
    """One run can dispatch two block programs (a second pass at another
    capacity, a regrow), and ``fusion.7`` is another op in each: a
    block's ops take the map its span's ``prog`` names, and the
    program's seconds are the sum of its rows, whatever the maps."""
    names = sorted(capture["ops"])
    first = ScopeMap({n: ("cyc.split", None) for n in names})
    second = ScopeMap({n: ("cyc.smooth", None) for n in names})
    spans = [{"block": 0, "prog": 0}, {"block": 1, "prog": 1}]
    res = devtime.digest(CAPTURE, _joined(first, second, records=spans))
    rows, prog = res["rows"]["jit_block"], res["programs"]["jit_block"]
    assert set(rows[0]["phases"]) == {"cyc.split"}
    assert set(rows[1]["phases"]) == {"cyc.smooth"}
    assert prog["phases"] == {"cyc.split": rows[0]["device_s"],
                              "cyc.smooth": rows[1]["device_s"]}
    assert prog["total"] == pytest.approx(capture["busy"], rel=1e-9)
    # a span that does not say which, where there are two: no guess
    res = devtime.digest(CAPTURE, _joined(first, second))
    prog = res["programs"]["jit_block"]
    assert prog["phases"] == {}
    assert prog["unscoped"] == pytest.approx(capture["busy"], rel=1e-9)
    assert prog["outside"] == 0.0


def test_ops_under_no_span_of_their_program_are_unscoped(capture):
    """No span, no ``prog``, no map to choose: a program's ops outside
    every span of its entry count, as ``outside``, under ``unscoped``."""
    names = sorted(capture["ops"])
    smap = ScopeMap({n: ("cyc.split", None) for n in names})
    res = devtime.digest(CAPTURE, {"jit_block": devtime.Joined(
        "no such span", [smap])})
    prog = res["programs"]["jit_block"]
    assert res["rows"]["jit_block"] == [] and prog["phases"] == {}
    assert prog["outside"] == prog["unscoped"] == prog["total"] == \
        pytest.approx(capture["busy"], rel=1e-9)


# ---------------------------------------------------------------------------
# the map of a block that ran, and what keeping its signature costs
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def ran(small):
    """A small governed block, run twice on the CPU; then its map, built
    once from jax's own caches and once after they were dropped (the
    backend compiles it again, outside every governed entry)."""
    from parmmg_tpu.parallel import groups

    def counters():
        return dict(REGISTRY.snapshot()["counters"])
    saved = dict(groups._GROUP_BLOCK_CACHE)
    groups._GROUP_BLOCK_CACHE.clear()
    LEDGER.reset()
    # (a reset keeps the signatures: they say what jax's caches hold)
    if BLOCK_ENTRY in LEDGER._entries:
        LEDGER._entries[BLOCK_ENTRY].signatures.clear()
    devtime._MAPS.clear()
    try:
        run = groups._group_block_program(False, False, 0.02)
        args = small["block_args"]
        c0 = counters()
        out = run(*args)
        kept1 = LEDGER.lowered_keys(BLOCK_ENTRY)
        out = run(out[0], out[1], *args[2:])
        jax.block_until_ready(out)
        kept2 = LEDGER.lowered_keys(BLOCK_ENTRY)
        calls = LEDGER.snapshot()[BLOCK_ENTRY]["calls"]
        prog = LEDGER.program_index(BLOCK_ENTRY)
        c1 = counters()
        warm = devtime.scope_map()
        c2 = counters()
        jax.clear_caches()
        devtime._MAPS.clear()
        cold = devtime.scope_map(BLOCK_ENTRY)
        c3 = counters()
    finally:
        groups._GROUP_BLOCK_CACHE.clear()
        groups._GROUP_BLOCK_CACHE.update(saved)
    return {"kept": (kept1, kept2), "calls": calls, "prog": prog,
            "warm": warm, "cold": cold, "counters": (c0, c1, c2, c3),
            "run": run, "out": out}


def _inc(ran, i, j, name):
    c = ran["counters"]
    return c[j].get(name, 0.0) - c[i].get(name, 0.0)


def test_scope_map_names_every_sort_of_a_block_with_a_phase(ran):
    counts = ran["warm"].counts
    assert counts["sorts"] >= 15
    assert sum(counts["sorts_by_phase"].values()) == counts["sorts"]
    assert all(p.startswith("cyc.") for p in counts["sorts_by_phase"])
    assert ran["warm"].module == "jit_run"


# what ``map_from_text`` counts on the parent's program at this shape
# (64, 97), commit cb0d304, my CPU run, PR 42: split 8, collapse 23,
# smooth 5
PARENT_SCALAR_GATHERS = 36


def test_scope_map_counts_the_scalar_gathers_the_row_packs_left(ran):
    """The capacities come from the signature's mesh.  At a toy shape a
    wave's top-K is as wide as a tet table, so what is left are the
    claims' K-wide fetches (5,389 wide at the cells' shape: not
    counted there): the split's four, the collapse's eight; the
    smoother has none."""
    counts = ran["warm"].counts
    assert counts["scalar_gathers"] == 12 <= PARENT_SCALAR_GATHERS // 3
    assert counts["scalar_gathers_by_phase"] == {"cyc.split": 4,
                                                 "cyc.collapse": 8}
    assert ran["cold"].counts["scalar_gathers"] == 12


def test_scope_map_puts_most_instructions_under_a_phase(ran):
    """What stays outside is the ``lax.map`` row's glue and the
    compiler's copies; an executable an older checkout compiled names a
    phase on none (``STALE_SHARE``)."""
    counts = ran["warm"].counts
    assert counts["scoped"] >= 0.8 * counts["ops"] > 0
    assert 0.8 > devtime.STALE_SHARE
    seen = {p for p, _ in ran["warm"].phases.values()}
    assert set(CYC) <= seen
    assert {t for _, t in ran["warm"].phases.values()} >= set(TAB)


def test_a_block_takes_mesh_metric_and_switches_and_hands_back_three(ran):
    """Six arguments, three results and an eleven-column row a group:
    the block carries no table state from cycle to cycle (PR 46)."""
    import inspect
    assert list(inspect.signature(ran["run"].__wrapped__).parameters) == [
        "stacked", "met_s", "wave", "active", "sw", "pr"]
    assert len(ran["out"]) == 3
    assert ran["out"][2].shape == (2, 11)


def test_a_block_sorts_its_tables_in_full(lowered):
    """Under ``tab.edges`` / ``tab.adjacency`` a block's program holds
    the full sort's straight line: no branch between a sort and a merge
    and no search loop (what ``ops/topo_incr`` puts under the same
    scopes in the host's polish, which the second assert sees)."""
    import re

    def nested(text):
        under = re.findall(r'"[^"]*?tab\.(?:edges|adjacency)/([^"]*)"', text)
        return {seg for path in under for seg in path.split("/")
                if seg in ("cond", "while")}
    assert "tab.edges" in lowered["block"][1]
    assert nested(lowered["block"][1]) == set()
    assert nested(lowered["polish"][1]) == {"cond", "while"}


def test_a_later_call_keeps_no_second_signature(ran):
    """One kept signature a lowering: the ledger's ``calls`` grows with
    every call, the kept signatures with a lowering only."""
    kept1, kept2 = ran["kept"]
    assert len(kept1) == 1 and kept2 == kept1
    assert ran["calls"] == 2
    assert ran["prog"] == 0
    assert _inc(ran, 0, 1, "compile.block_programs") == 1


def test_the_map_of_a_live_program_compiles_nothing(ran):
    assert _inc(ran, 1, 2, "compile.backend_n") == 0
    assert _inc(ran, 1, 2, "compile.block_programs") == 0


def test_block_programs_does_not_move_when_scope_map_compiles(ran):
    """With jax's caches dropped the backend builds the program again
    for the map: no governed entry is credited, and the map is the
    same."""
    assert _inc(ran, 2, 3, "compile.backend_n") >= 1
    assert _inc(ran, 2, 3, "compile.block_programs") == 0
    assert LEDGER.snapshot()[BLOCK_ENTRY]["compiles"] == 1
    assert ran["cold"].counts == ran["warm"].counts


def test_the_map_is_read_where_the_program_ran():
    """A program staged on another device by ``jax.default_device`` (the
    host staging of the merged polish on a chip) takes UNCOMMITTED
    arguments: lowered again outside that context it would be another
    program, compiled for the default backend (on the chip: the whole
    polish for the TPU, minutes and gigabytes, and a map of instructions
    that never ran).  The kept signature remembers the device."""
    from parmmg_tpu.utils.compilecache import governed

    @governed("test.staged")
    @jax.jit
    def staged(x):
        with jax.named_scope("pol.smooth"):
            return jnp.sort(x) * 2.0

    other = jax.devices()[1]
    with jax.default_device(other):
        out = staged(jnp.arange(64.0))
    assert out.devices() == {other}
    before = REGISTRY.snapshot()["counters"].get("compile.backend_n", 0.0)
    smap = devtime.scope_map("test.staged")
    after = REGISTRY.snapshot()["counters"].get("compile.backend_n", 0.0)
    assert after == before          # jax's own caches answered
    assert smap.counts["sorts"] == 1
    assert smap.counts["sorts_by_phase"] == {"pol.smooth": 1}
    assert LEDGER.signature("test.staged")[3] == other


def test_a_fem_round_has_a_map_of_its_own(small):
    """``fem_pass`` is governed, so the ledger keeps its signature and
    the digest reaches ``jit_fem_pass_impl`` as it does the block."""
    from parmmg_tpu.ops import adapt
    m, met = small["mesh"], small["met"]
    # donated: hand over copies
    out = adapt.fem_pass(jax.tree_util.tree_map(jnp.copy, m), jnp.copy(met))
    jax.block_until_ready(out)
    assert "adapt.fem_pass" in devtime.ENTRIES
    # (an index among all the fem programs this process lowered)
    assert LEDGER.program_index("adapt.fem_pass") in range(
        len(LEDGER.lowered_keys("adapt.fem_pass")))
    smap = devtime.scope_map("adapt.fem_pass")
    assert smap.module == "jit_fem_pass_impl"
    assert set(FEM) <= {p for p, _ in smap.phases.values()}
    assert smap.counts["sorts"] > 0
    assert set(smap.counts["sorts_by_phase"]) <= set(FEM)


def test_a_fem_round_with_a_state_keeps_its_stages_and_tables(small):
    """With the polish's ``TopoState`` handed on (PR 44) the round's two
    tables are ``incr_unique_edges`` / ``incr_build_adjacency``, jitted
    functions of their own: the map still finds ``tab.edges`` under
    ``fem.split`` and ``tab.adjacency`` under ``fem.adjacency``, and the
    full sorts (the arm a round takes where nothing is retained) there."""
    from parmmg_tpu.ops import adapt
    from parmmg_tpu.ops.topo_incr import topo_init
    m, met = small["mesh"], small["met"]
    out = adapt.fem_pass(jax.tree_util.tree_map(jnp.copy, m), jnp.copy(met),
                         topo_init(m.capT))
    jax.block_until_ready(out)
    assert np.asarray(out[2]).tolist()[3:] == [2, 0]    # nothing retained
    smap = devtime.scope_map("adapt.fem_pass")          # the last call's
    assert smap.module == "jit_fem_pass_impl"
    pairs = set(smap.phases.values())
    assert set(FEM) <= {p for p, _ in pairs}
    assert ("fem.split", "tab.edges") in pairs
    assert ("fem.adjacency", "tab.adjacency") in pairs
    assert not {("fem.split", "tab.adjacency"),
                ("fem.adjacency", "tab.edges")} & pairs
    assert {"fem.split", "fem.adjacency"} <= \
        set(smap.counts["sorts_by_phase"]) <= set(FEM)


def test_no_map_where_a_second_compile_would_cost_what_the_first_did(
        tmp_path):
    """No persistent cache and a program that took minutes to compile:
    ``scope_map`` refuses (a ``LookupError``, which its callers expect)
    and does not find out the hard way; with a cache directory the
    second compile is a load, and the map is built."""
    from parmmg_tpu.utils.compilecache import governed

    @governed("test.costly")
    @jax.jit
    def costly(x):
        with jax.named_scope("cyc.smooth"):
            return jnp.sort(x) + 1.0

    costly(jnp.arange(32.0))
    entry = LEDGER._entries["test.costly"]
    # what the entry's OTHER programs cost is no measure of this one
    entry.compile_secs = 100 * devtime.COLD_COMPILE_LIMIT_S
    assert devtime.scope_map("test.costly").counts["sorts"] == 1
    devtime._MAPS.clear()
    (key,) = entry.keys_compiled
    entry.keys_compiled[key] = 4 * devtime.COLD_COMPILE_LIMIT_S
    assert not jax.config.jax_compilation_cache_dir
    with pytest.raises(LookupError, match="no persistent compile cache"):
        devtime.scope_map("test.costly")
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:        # (jax's own caches answer: the directory stays empty)
        assert devtime.scope_map("test.costly").counts["sorts"] == 1
    finally:
        jax.config.update("jax_compilation_cache_dir", None)


def test_the_digest_says_when_the_executable_is_somebody_elses(
        monkeypatch, tmp_path):
    """A block program from a cache an older checkout wrote names no
    phase: the digest logs what to do about it, at every verbosity."""
    from parmmg_tpu.obs import trace as otrace
    stale = ScopeMap({}, {"ops": 9116, "scoped": 12, "sorts": 53,
                          "sorts_by_phase": {UNSCOPED: 53}},
                     module="jit_run")
    monkeypatch.setattr(devtime, "LEDGER", types.SimpleNamespace(
        lowered_keys=lambda entry: [("older",), ("this run's",)]
        if entry == BLOCK_ENTRY else []))
    asked = []
    monkeypatch.setattr(devtime, "scope_map",
                        lambda entry, key: asked.append(key) or stale)
    with otrace.span("grp block") as sp:
        sp.set(prog=1)
    assert devtime.digest_run(str(tmp_path)) is None    # no capture here
    # a map for the program the run's spans name, and for no other
    assert asked == [("this run's",)]
    said = [r["msg"] for r in otrace.TRACER.ring
            if r.get("kind") == "log" and r.get("lvl") == 0]
    assert any("12 of 9116" in m and "JAX_COMPILATION_CACHE_DIR" in m
               for m in said)


def test_scope_map_of_an_entry_that_lowered_nothing_raises():
    with pytest.raises(KeyError):
        devtime.scope_map("no.such.entry")


def test_lowered_text_is_the_same_whatever_the_scopes_say(small):
    """A scope is metadata only: the text without debug info does not
    change when every scope is a no-op (the block program's, the
    polish's and the fem pass's were hashed against the parent's at the
    five cells' shapes: CHANGES.md, PR 39)."""
    from contextlib import nullcontext
    from parmmg_tpu.obs import trace as otrace
    from parmmg_tpu.ops import adapt

    def text():
        # a fresh function each time: no trace of the other is reused
        return jax.jit(lambda m, k: adapt.fem_pass_impl(m, k)).lower(
            small["mesh"], small["met"])

    scoped = text()
    was = otrace.scope
    otrace.scope = lambda name: nullcontext()
    try:
        bare = text()
    finally:
        otrace.scope = was
    assert "fem.split" in scoped.as_text(debug_info=True)
    assert "fem.split" not in bare.as_text(debug_info=True)
    assert hashlib.sha256(scoped.as_text().encode()).hexdigest() == \
        hashlib.sha256(bare.as_text().encode()).hexdigest()
