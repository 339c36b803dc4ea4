"""The trace reduction on a small recorded trace: busy union, time by
op and program name, idle gaps named by the host's spans, and the
roofline share a kernel's events give."""
import types

import pytest

import bench_smoke  # noqa: F401
import devtrace
import flops


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
    assert devtrace.matching(tr, "dequant_matmul") == (2, pytest.approx(20e-9))
    assert devtrace.matching(tr, "paged_decode_scan", modules=True) == (
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
    _, secs = devtrace.matching(tr, "dequant_matmul")
    # two calls of x (8, 128) @ W4 (128, 256), g128: bytes bound
    f, b = flops.dequant_matmul_cost(8, 128, 256, 4, 128)
    least = 2 * flops.least_seconds(f, b, 197e12, 819e9)
    assert least == pytest.approx(2 * b / 819e9)
    assert 100 * least / secs == pytest.approx(100 * 2 * b / 819e9 / 20e-9)
