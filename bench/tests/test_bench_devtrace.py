"""The trace reduction on a small recorded trace: busy union, time by
op and program name, idle gaps named by the host's spans, the roofline
share a kernel's events give, and kernels told apart by their whole
name."""
import types

import pytest

from bench_smoke import CONFIGS, smoke
import devtrace
import flops
import harness


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs]) for ln, evs in lines.items()])


# two programs on one chip: ops at [0, 10), [5, 15) (overlapping),
# [40, 50) and [100, 130); the host is in bench.step over [0, 60) and in
# bench.wait over [60, 120)
RECORDED = [
    _plane("/device:TPU:0", {
        "XLA Ops": [("%fusion.1 = bf16[8] fusion(...)", 0, 10),
                    ("%dequant_matmul_pallas.3 = f32[8,256] custom-call(...)",
                     5, 10),
                    ("%dequant_matmul_pallas.7 = f32[8,256] custom-call(...)",
                     40, 10),
                    ("%paged_attention_pallas.1 = f32[1] custom-call(...)",
                     100, 30),
                    ("%while.5 = (s32[]) while(...)", 0, 50)],
        "XLA Modules": [("jit__paged_decode_scan_jit(12)", 0, 50),
                        ("jit__paged_prefill_jit(7)", 100, 30)]}),
    _plane("/host:CPU", {"python": [("bench.step", 0, 60),
                                    ("bench.wait", 60, 60),
                                    ("PjitFunction(step)", 1, 5)]}),
    _plane("/host:metadata", {}),
]


def test_busy_is_the_union_of_op_intervals():
    tr = devtrace.from_planes(RECORDED)
    assert devtrace.union_ns(tr.ops["/device:TPU:0"]) == 50 + 30
    assert devtrace.busy_seconds(tr) == pytest.approx(80e-9)


def test_time_by_name_and_program():
    tr = devtrace.from_planes(RECORDED)
    assert devtrace.kernel(tr, "dequant_matmul_pallas") == (
        2, pytest.approx(20e-9))
    assert devtrace.programs(tr, "paged_decode_scan") == (
        1, pytest.approx(50e-9))
    # control-flow ops are left out: their bodies' ops are events too
    assert devtrace.top_ops(tr) == [
        ("paged_attention_pallas", pytest.approx(30e-9)),
        ("dequant_matmul_pallas", pytest.approx(20e-9)),
        ("fusion", pytest.approx(10e-9))]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = devtrace.from_planes(RECORDED)
    assert [s.name for s in tr.spans] == ["bench.step", "bench.wait"]
    gaps = devtrace.idle_gaps(tr)
    # the one gap, [50, 100), has its middle 75 in bench.wait
    assert gaps == [("bench.wait", pytest.approx(50e-9))]


def test_roofline_share_of_recorded_kernel_events():
    tr = devtrace.from_planes(RECORDED)
    _, secs = devtrace.kernel(tr, "dequant_matmul_pallas")
    # two calls of x (8, 128) @ W4 (128, 256), g128: bytes bound
    f, b = flops.dequant_matmul_cost(8, 128, 256, 4, 128)
    least = 2 * flops.least_seconds(f, b, 197e12, 819e9)
    assert least == pytest.approx(2 * b / 819e9)
    assert 100 * least / secs == pytest.approx(100 * 2 * b / 819e9 / 20e-9)


# the dense and the expert dequant kernels and the paged read on one
# chip: the expert kernel's name holds the dense kernel's
TWO_KERNELS = [
    _plane("/device:TPU:0", {"XLA Ops": [
        ("%dequant_matmul_pallas.3 = f32[8,256] custom-call(...)", 0, 10),
        ("%expert_dequant_matmul_pallas.4 = f32[4,8,256] custom-call(...)",
         10, 100),
        ("%paged_attention_pallas.2 = f32[8,256] custom-call(...)", 110, 30),
        ("%expert_dequant_matmul_pallas.9 = f32[4,8,256] custom-call(...)",
         140, 100)]}),
]


def test_kernels_are_matched_by_their_whole_name():
    tr = devtrace.from_planes(TWO_KERNELS)
    assert devtrace.kernel(tr, "dequant_matmul_pallas") == (
        1, pytest.approx(10e-9))
    assert devtrace.kernel(tr, "expert_dequant_matmul_pallas") == (
        2, pytest.approx(200e-9))
    assert devtrace.kernel(tr, "paged_attention_pallas") == (
        1, pytest.approx(30e-9))
    # the readers divide by their own kernel's seconds only
    _, ref, a, _ = smoke(CONFIGS[0])
    pk = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    tracker = types.SimpleNamespace(detail=True, decode_steps=1,
                                    decode_ctx=[100], prefill_chunks=[])
    run = harness.Run(setup_s=1.0, window_s=1.0, attempted=0, failed=0,
                      checks=[], memory_peak_bytes=0, peaks=pk,
                      data={"tracker": tracker, "reference": ref, "arch": a,
                            "n_slots": 8}, trace=tr)
    dq = sum(calls * flops.least_seconds(
        *flops.dequant_matmul_cost(8, k, n, a.bits, a.group_size),
        pk["bf16_flops"], pk["hbm_bytes_per_s"])
        for k, n, calls in ref.dequant_projections(a))
    assert harness.metric_reader("dequant_mm_roofline.chat").read(run) == \
        pytest.approx(100 * dq / 10e-9)
    pa = sum(calls * flops.least_seconds(
        *flops.paged_read_cost(1, 100, kvh, g, hd),
        pk["bf16_flops"], pk["hbm_bytes_per_s"])
        for kvh, g, hd, calls in ref.paged_reads(a))
    assert harness.metric_reader("paged_attn_roofline.batch").read(run) == \
        pytest.approx(100 * pa / 30e-9)
