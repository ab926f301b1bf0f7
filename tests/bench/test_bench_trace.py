"""The reduction from a trace to busy time, idle share, kernel time and
idle time by host activity, on a small synthetic trace."""

import pytest

from bench import kernels, trace
from bench.trace import Interval, Trace

PRED = ('%local.1 = (u32[8,1,65536]{2,1,0:T(1,128)S(1)}, '
        's32[8,1,128]{2,1,0:T(1,128)S(1)}) custom-call(s32[32]{0:T(128)} '
        '%idx.1, u32[8,1024,65536]{2,1,0:T(8,128)} %lut.1), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s32[32]{0}, u32[8,1024,65536]{2,1,0}}, frontend_attributes='
        '{kernel_metadata={}}')
LEAF = ('%local.1 = u32[4096,256]{1,0:T(8,128)} custom-call(s32[4096,16]'
        '{1,0:T(8,128)S(1)} %copy, u32[264,256]{1,0:T(8,128)} %lut.1, '
        'u32[8,256]{1,0:T(8,128)} %masks.1), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={s32[4096,16]{1,0}, '
        'u32[264,256]{1,0}, u32[8,256]{1,0}}, frontend_attributes='
        '{kernel_metadata={}}')
FUSION = ('%copy_bitcast_fusion = u32[8,65536]{1,0:T(8,128)} fusion(u32'
          '[8,1,65536]{2,1,0:T(1,128)S(1)} %pallas_call.4), kind=kLoop, '
          'calls=%fused_computation')


def synthetic() -> Trace:
    """Two requests on one chip over [0, 100):

    request 0 [0, 40): kernel [10, 30), fusion [28, 32) (overlaps it)
    request 1 [50, 100): kernel [60, 70)
    host: run [0, 40) > unpack [32, 40); run [50, 100) > prep [50, 60)
    """
    return Trace(
        device={"/device:TPU:0": [Interval(10, 30, PRED),
                                  Interval(28, 32, FUSION),
                                  Interval(60, 70, PRED)]},
        python=[[Interval(0, 40, "run"), Interval(32, 40, "unpack"),
                 Interval(50, 100, "run"), Interval(50, 60, "prep")]],
        spans=[Interval(0, 40, "bench_request", (("i", "0"),)),
               Interval(50, 100, "bench_request", (("i", "1"),))])


def test_window_busy_and_idle_share():
    tr = synthetic()
    lo, hi = trace.window(tr)
    assert (lo, hi) == (0, 100)
    # union of [10, 32) and [60, 70): overlapping ops count once
    assert trace.busy_ns(tr, lo, hi) == 32
    assert trace.busy_ns(tr, 20, 65) == 12 + 5
    assert trace.union(tr.device["/device:TPU:0"], 0, 100) == [
        (10, 32), (60, 70)]


def test_busy_is_averaged_over_chips():
    tr = synthetic()
    tr.device["/device:TPU:1"] = [Interval(0, 100, PRED)]
    assert trace.busy_ns(tr, 0, 100) == (32 + 100) / 2


def test_kernel_time_matches_only_its_kernel():
    tr = synthetic()
    assert trace.kernel_ns(tr, kernels.PREDICATE, 0, 100) == 30
    assert trace.kernel_ns(tr, kernels.PREDICATE, 0, 50) == 20
    assert trace.kernel_ns(tr, kernels.LEAFBITS, 0, 100) is None


def test_overlap_of_merged_intervals():
    merged = [(10, 32), (60, 70)]
    assert trace.overlap(merged, 0, 40) == 22
    assert trace.overlap(merged, 31, 65) == 6
    assert trace.overlap(merged, 40, 50) == 0


def test_innermost_segments():
    tr = synthetic()
    segs = trace.innermost(tr.python[0], 0, 100)
    assert segs == [(0, 32, "run"), (32, 40, "unpack"), (40, 50, None),
                    (50, 60, "prep"), (60, 100, "run")]


def test_innermost_clips_a_child_that_outlives_its_parent():
    evs = [Interval(0, 10, "a"), Interval(5, 12, "b")]
    assert trace.innermost(evs, 0, 12) == [(0, 5, "a"), (5, 10, "b"),
                                           (10, 12, None)]


def test_idle_gaps_by_host_activity():
    idle = trace.idle_by_host(synthetic(), 0, 100)
    # idle: [0,10) run, [32,40) unpack, [40,50) nothing, [50,60) prep,
    # [70,100) run
    assert idle == {"run": 10 + 30, "unpack": 8, "no host function": 10,
                    "prep": 10}


def test_breakdown_ranks_in_seconds():
    b = trace.breakdown(synthetic(), 0, 100, top=2)
    assert b["device_ops"][0] == [
        "tpu_custom_call (u32[8,1,65536], s32[8,1,128]) <- "
        "(s32[32], u32[8,1024,65536])", pytest.approx(30e-9)]
    assert len(b["device_ops"]) == 2
    assert b["idle_gaps"][0] == ["run", pytest.approx(40e-9)]
    assert len(b["idle_gaps"]) == 2


def test_op_labels():
    assert trace.op_label(LEAF) == (
        "tpu_custom_call u32[4096,256] <- "
        "(s32[4096,16], u32[264,256], u32[8,256])")
    assert trace.op_label(FUSION) == "fusion u32[8,65536] <- (u32[8,1,65536])"
    assert trace.op_label("not an HLO op") == "not an HLO op"


def test_a_trace_without_a_device_reads_nothing():
    tr = Trace(spans=synthetic().spans)
    assert trace.busy_ns(tr, 0, 100) is None
    assert trace.idle_by_host(tr, 0, 100) == {}
    assert trace.window(Trace()) is None
