"""The trace reducer: on intervals, on a hand-built capture whose numbers
are known, and on a small capture recorded on a v5e."""
import os
from types import SimpleNamespace as NS

import pytest

import trace_reduce as tr
from kernel_bytes import kernel_bytes, padded_rows

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.xplane.pb")
MS = 1e6       # ns


def test_union_counts_overlap_once():
    assert tr.union_s([]) == 0.0
    assert tr.union_s([(0, 10), (5, 20), (30, 40), (32, 35)]) \
        == pytest.approx(30e-9)
    # a while op and the ops of its body
    assert tr.union_s([(0, 100), (10, 20), (30, 90)]) == pytest.approx(1e-7)


def test_gaps_are_what_no_interval_covers():
    assert tr.gaps([(10, 20), (15, 30), (50, 60)], 0, 100) == \
        [(0, 10), (30, 50), (60, 100)]
    assert tr.gaps([(0, 100)], 0, 100) == []
    assert tr.gaps([], 5, 9) == [(5, 9)]
    assert tr.gaps([(0, 10), (90, 200)], 5, 100) == [(10, 90)]


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=[])


def capture():
    """One chip, one job of 100 ms: two cycle blocks of 20 ms each (a
    while holding a sort, a kernel, a gather and a plain fusion), a 40 ms
    host phase between them, 10 ms of staging before and of pulling
    after.  Op events are named as a v5e capture names them."""
    def block(t0):
        return [
            ev("%while.3 = (s32[], f32[2,43118,3]{2,1,0}) while((s32[], "
               "f32[2,43118,3]{2,1,0}) %tuple.1), condition=%cond, "
               "body=%body", t0, 20),
            ev("%sort.7 = (f32[24576]{0:T(1024)}, s32[24576]{0:T(1024)S(1)})"
               " sort(f32[24576]{0:T(1024)S(1)} %bitcast.21, s32[24576]"
               "{0:T(1024)S(1)} %iota.0), dimensions={0}", t0, 8),
            ev("%score_count.1 = (f32[2024,128]{1,0:T(8,128)S(1)}, s32[1,1]"
               "{1,0:T(1,128)}) custom-call(f32[2024,128]{1,0:T(8,128)S(1)} "
               "%a, f32[2024,128]{1,0:T(8,128)S(1)} %b), custom_call_target="
               '"tpu_custom_call", operand_layout_constraints={}', t0 + 8, 4),
            ev("%fusion.11 = f32[24576]{0:T(1024)S(1)} fusion(f32[4096]"
               "{0:T(1024)S(1)} %fusion.2, s32[24576]{0:T(1024)S(1)} %idx), "
               "kind=kCustom, calls=%fused_computation.1", t0 + 12, 6),
            ev("%add_fusion = f32[24576]{0:T(1024)} fusion(f32[24576]"
               "{0:T(1024)S(1)} %fusion.11), kind=kLoop, "
               "calls=%fused_computation.9", t0 + 18, 2),
        ]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_run(123)", 10, 20), ev("jit_run(123)", 70, 20)]),
        NS(name="XLA Ops", events=block(10) + block(70)),
        NS(name="Async XLA Ops", events=[ev("%copy-start = ...", 0, 100)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[
            ev("bench.job", 0, 100), ev("bench.stage", 0, 9),
            ev("bench.run", 9, 82), ev("PjitFunction(merge_shards)", 32, 30),
            ev("bench.pull", 91, 9)]),
        # a runtime thread without our annotations is not consulted
        NS(name="tpu-runtime/7", events=[ev("ReadSyncFlag", 35, 25)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), device, host])


def test_an_op_events_name_is_parsed():
    op = tr.parse_op(capture().planes[1].lines[1].events[2].name)
    assert (op["short"], op["opcode"]) == ("score_count", "custom-call")
    assert op["kernel"] == ("score_count", 2024 * 128)
    assert not op["tables"] and not op["control"]
    ops = [tr.parse_op(e.name) for e in capture().planes[1].lines[1].events]
    assert [o["opcode"] for o in ops[:5]] == [
        "while", "sort", "custom-call", "fusion", "fusion"]
    assert [o["tables"] for o in ops[:5]] == [False, True, False, True, False]
    assert [o["control"] for o in ops[:5]] == [True] + [False] * 4


def test_a_capture_with_known_numbers():
    # Timer spans of the job on the epoch clock; the job began at 1000 s
    spans = [("analysis", 1000.001, 1000.009),
             ("adaptation", 1000.010, 1000.090)]
    out = tr.reduce_profile(capture(), spans, 1000.0)
    assert out["chips"] == 1 and out["blocks"] == 2
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.040)
    assert out["block_s"] == pytest.approx(0.040)
    assert out["sort_scatter_s"] == pytest.approx(0.028)    # 2 x (8 + 6)
    assert out["pallas_s"] == pytest.approx(0.008)
    assert out["kernels"] == [{"name": "score_count",
                               "elements": 2024 * 128,
                               "seconds": pytest.approx(0.008), "calls": 2}]
    ops = dict(out["breakdown"]["device_ops"])
    assert "while.3" not in ops                 # encloses, does no work
    assert ops["sort.7"] == pytest.approx(0.016)
    assert ops["score_count"] == pytest.approx(0.008)
    assert ops["fusion.11"] == pytest.approx(0.012)
    idle = dict(out["breakdown"]["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(0.060)
    assert idle["adaptation: PjitFunction(merge_shards)"] \
        == pytest.approx(0.040)
    assert idle["analysis: bench.stage"] == pytest.approx(0.010)
    assert idle["bench.pull"] == pytest.approx(0.010)


def test_the_capture_recorded_on_a_v5e():
    """tests/record_trace.py, on one "TPU v5 lite": two runs of a toy
    block (program ``jit_block``) with a 50 ms host nap between.  The
    expected numbers were read off the capture by hand
    (``python3 benchmarks/trace_reduce.py <file> 50``)."""
    import re
    out = tr.reduce_profile(tr.load(DATA),
                            block_module=re.compile(r"^jit_block\b"))
    assert out["chips"] == 1 and out["blocks"] == 2
    # the two module events last 545.863 and 545.760 us; the ops inside
    # leave a few hundred ns of them uncovered
    assert out["busy_s"] == pytest.approx(1.0909e-3, rel=1e-3)
    assert out["block_s"] == pytest.approx(out["busy_s"], rel=1e-6)
    assert out["block_s"] <= (545.863 + 545.760) * 1e-6
    assert out["window_s"] == pytest.approx(53.728419e-3)
    kernels = {k["name"]: k for k in out["kernels"]}
    assert set(kernels) == {"edge_length_iso", "score_count"}
    assert all(k["calls"] == 2 and k["elements"] == 192 * 128
               for k in kernels.values())
    assert kernels["score_count"]["seconds"] == pytest.approx(8.466e-6)
    assert out["pallas_s"] == pytest.approx(10.802e-6)
    # the sort (2 x 24.09 us) and the three kCustom fusions
    assert out["sort_scatter_s"] == pytest.approx(1.07145e-3, rel=1e-4)
    idle = dict(out["breakdown"]["idle_gaps"])
    assert idle["host_nap"] == pytest.approx(0.0512, rel=0.01)
    assert max(idle, key=idle.get) == "host_nap"
    # idle share of this toy: the device worked 2 % of the job
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.9797,
                                                                abs=1e-3)


def test_a_capture_without_device_ops_is_refused():
    empty = NS(planes=[NS(name="/host:CPU", lines=[])])
    with pytest.raises(ValueError):
        tr.reduce_profile(empty)


def test_kernel_bytes_from_shapes():
    assert padded_rows(6 * 43118) == 2024
    n = 6 * 43118
    assert kernel_bytes("edge_length_iso", n) == 9 * 2024 * 128 * 4
    assert kernel_bytes("edge_length_ani", n) == 16 * 2024 * 128 * 4
    assert kernel_bytes("quality_iso", 43118) == 19 * 344 * 128 * 4
    assert kernel_bytes("quality_ani", 43118) == 19 * 344 * 128 * 4
    assert kernel_bytes("score_count", n) == 3 * 2024 * 128 * 4
    assert kernel_bytes("score3_count", n) == 5 * 2024 * 128 * 4
    assert kernel_bytes("merge_prefix", n) == 2 * 2024 * 128 * 4
    with pytest.raises(KeyError):
        kernel_bytes("radix_sort", n)


def test_the_roofline_share_counts_every_call():
    from byname import load
    read = load("layer_metrics", "pallas_roofline").read
    run = {"peaks": {"hbm_bytes_per_s": 819e9}, "trace": {"kernels": [
        {"name": "score_count", "elements": 2024 * 128, "seconds": 8e-6,
         "calls": 2},
        {"name": "edge_length_iso", "elements": 2024 * 128,
         "seconds": 24e-6, "calls": 2}]}}
    least = 2 * (3 + 9) * 2024 * 128 * 4 / 819e9
    assert read(run) == pytest.approx(100 * least / 32e-6)
    assert 90 < read(run) < 100        # 30.4 us of traffic in 32 us
    assert read({"trace": None}) is None
    assert read({"trace": {"kernels": []}, "peaks": {}}) is None
