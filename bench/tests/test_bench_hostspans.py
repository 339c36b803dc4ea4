"""The program's engine.* spans reduced beside the device's ops: spans
read with their stats, host time of a step without its waits, device idle
time by the innermost span over it, each request's prefill lag and
first-token hold, which with its queue wait make up its TTFT; and, on a
whole traced run at smoke size on the CPU, the engine's counters giving
the tracker's occupancy and live-page share over the same window."""
import time
import types

import pytest

from bench_smoke import CELLS, SERVE_CELLS, smoke, smoke_workload
import devtrace
import harness
import hostspans
from drivers.serve import Record

MS = 1e6   # ns


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s * MS,
                                  duration_ns=(e - s) * MS,
                                  stats=list(st.items()))
            for n, s, e, st in evs]) for ln, evs in lines.items()])


# two steps, times in ms: request 4 is admitted in step 0 and gets its
# first token there; request 7, admitted with it, in step 1 (one id
# comes back from a trace as a number). Device ops at [10, 45), [55, 85),
# [100, 110), [130, 145), [160, 185): idle [45, 55), [85, 100),
# [110, 130), [145, 160)
RECORDED = [
    _plane("/device:TPU:0", {"XLA Ops": [
        ("%fusion.1 = f32[8] fusion(...)", s, e, {})
        for s, e in ((10, 45), (55, 85), (100, 110), (130, 145),
                     (160, 185))]}),
    _plane("/host:CPU", {"python": [
        ("bench.step", 0, 100, {}),
        ("engine.step", 0, 100, {"step": 0}),
        ("engine.admit", 1, 5, {"rids": "4;7"}),
        ("engine.prefill", 5, 20, {"rows": 2, "bucket": 128, "ctx": 0}),
        ("engine.prefill.wait", 20, 40, {"rids": 4}),
        ("engine.decode", 40, 50, {"k": 8, "slots": 1}),
        ("engine.decode.wait", 50, 90, {}),
        ("engine.emit", 90, 98, {"tokens": 8}),
        ("bench.wait", 100, 120, {}),
        ("engine.step", 120, 200, {"step": 1}),
        ("engine.admit", 121, 124, {}),
        ("engine.prefill", 124, 130, {"rows": 1, "bucket": 128, "ctx": 1}),
        ("engine.prefill.wait", 130, 150, {"rids": 7}),
        ("engine.decode", 150, 155, {"k": 8, "slots": 2}),
        ("engine.decode.wait", 155, 190, {}),
        ("engine.emit", 190, 198, {"tokens": 16}),
        ("PjitFunction(step)", 1, 5, {})]}),
]


def _ops():
    tr = devtrace.from_planes(RECORDED)
    return tr.ops["/device:TPU:0"]


def test_engine_spans_are_read_with_their_stats():
    got = hostspans.from_planes(RECORDED)
    assert [s.name for s in got][:3] == ["engine.step", "engine.admit",
                                         "engine.prefill"]
    assert len(got) == 14
    assert got[1].rids() == [4, 7] and got[3].rids() == [4]
    assert got[0].stats == {"step": 0}
    st = hostspans.steps(got)
    assert [len(inner) for _, inner in st] == [6, 6]
    # the benchmark's own spans and their outputs are untouched
    assert [s.name for s in devtrace.from_planes(RECORDED).spans] == [
        "bench.step", "bench.wait"]


def test_host_step_time_leaves_out_the_waits():
    # (100 - 20 - 40) and (80 - 20 - 35) ms
    assert hostspans.host_step_ms(hostspans.from_planes(RECORDED)) == (
        pytest.approx(32.5))


def test_idle_time_by_innermost_span():
    spans = hostspans.from_planes(RECORDED)
    by = hostspans.idle_by_span(_ops(), spans)
    want = {"engine.decode": 10, "engine.decode.wait": 15,
            "engine.emit": 8, "engine.step": 3, "engine.admit": 3,
            "engine.prefill": 6, "engine.prefill.wait": 5, "none": 10}
    assert by == {k: pytest.approx(v * 1e-3) for k, v in want.items()}
    assert sum(by.values()) == pytest.approx(60e-3)
    assert hostspans.longest_gaps(_ops(), spans, top=2) == [
        ("engine.step", pytest.approx(20e-3)),     # middle at 120
        ("engine.emit", pytest.approx(15e-3))]     # middle at 92.5
    # all of it but the serving loop's 10 ms between the steps
    assert hostspans.idle_in_steps_s(_ops(), spans) == pytest.approx(50e-3)


def test_prefill_lag_and_first_token_hold():
    split = hostspans.first_token_split(hostspans.from_planes(RECORDED))
    assert split == {4: (pytest.approx(0.040), pytest.approx(0.060)),
                     7: (pytest.approx(0.150), pytest.approx(0.050))}


def test_queue_wait_lag_and_hold_make_up_the_ttft():
    # the serving loop's clock reads 5 s where the trace reads 0; a request is
    # admitted at the start of its step and stamped at the end of the
    # step that made its first token
    recs = [Record(arrival=4.0, n_prompt=8, max_new=8, admitted=5.0,
                   t_first=5.1),
            Record(arrival=4.5, n_prompt=8, max_new=8, admitted=5.0,
                   t_first=5.2),
            Record(arrival=20.0, n_prompt=8, max_new=8)]   # not due
    run = harness.Run(setup_s=1.0, window_s=0.2, attempted=0, failed=0,
                      checks=[], memory_peak_bytes=0, peaks={},
                      data={"records": recs, "seconds": 10.0,
                            "n_slots": 2},
                      trace=devtrace.from_planes(RECORDED))
    rid_of = {id(recs[0]): 4, id(recs[1]): 7, id(recs[2]): 9}
    got = hostspans.readings(run, hostspans.from_planes(RECORDED), rid_of,
                             None, None)
    assert got["identity_requests"] == 2
    assert got["identity_max_ms"] == pytest.approx(0.0, abs=1e-6)
    # nearest-rank medians of (40, 150) and (60, 50) ms
    assert got["prefill_lag_p50_ms"] == pytest.approx(40.0)
    assert got["first_token_hold_p50_ms"] == pytest.approx(50.0)
    assert got["idle_in_step_frac"] == pytest.approx(50e-3 / 0.2)
    assert got["steps"] == 2 and "engine_occupancy" not in got


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_engine_counters_agree_with_the_tracker(cell):
    cfg, _, _, _ = smoke(CELLS[cell])
    workload = smoke_workload(cell)
    run, spans, rid_of, before, after = hostspans.traced_run(
        config=cfg, workload=workload, seed=2**35 + 21,
        seconds=2.0, t0=time.perf_counter(), peaks={})
    got = hostspans.readings(run, spans, rid_of, before, after)
    t = run.data["tracker"]
    assert t.decode_steps == got["counters"]["n_decode_steps"] > 0
    assert got["engine_occupancy"] == pytest.approx(
        harness.metric_reader("batch_occupancy").read(run))
    assert got["pool_live_frac"] == pytest.approx(
        harness.metric_reader("kv_live_frac").read(run))
    assert got["steps"] > 0 and got["host_step_ms"] > 0
    if workload["traffic"].get("drain_seconds"):
        # every request due in the window and answered in it is split;
        # on the CPU the two clocks' readings lie well inside a step
        assert got["identity_requests"] > 0
        assert got["identity_max_ms"] < 20.0
