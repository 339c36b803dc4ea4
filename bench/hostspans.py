"""The program's own host spans (`engine.*`, see src/repro/spans.py) in a
profiler trace, reduced beside the device's ops; and a by-hand traced run
of a serving cell that keeps them.

    python3 bench/hostspans.py --workload <cell> --seeds 1,2,3 --seconds 45

`devtrace.from_planes` keeps only the benchmark's own `bench.*` spans and
no stats; this module reads the `engine.*` events of the host plane with
their stats (`step`, `rids`, `k`, ...). Spans of one step nest as
repro.spans says; a list of request ids is joined by ';' (a single id
comes back from the trace as a number).

The command runs, for each seed in one process, the cell as
bench/drivers/serve.py does (weights, engine, warm-up, pre-roll, window
traced by the profiler, drain) through that module's own functions,
skips the output check, and prints one JSON line: the cell's end-to-end and
per-layer metrics from the traced window (the benchmark's own readers),
the breakdown, and the readings below, which need the engine's spans or
counters and so no metric of BENCHMARK.json can take yet (the serving
driver keeps neither past its run). Needs the chip, like bench/run.py.

  host_step_ms         mean over the window's engine.step spans of the
                       step minus the waits inside it, ms
  idle_in_step_frac    device idle time inside engine.step spans over
                       the window (the rest of the idle time is the
                       serving driver's loop between steps)
  prefill_lag_p50_ms   nearest-rank median over the requests due in the
                       window of (end of the engine.prefill.wait naming
                       the request) - (start of the engine.step whose
                       engine.admit names it)
  first_token_hold_p50_ms  the same requests: (end of the engine.step
                       holding that wait) - (end of the wait)
  identity_max_ms      largest |TTFT - (queue wait + lag + hold)| over
                       those requests (two clocks, durations only)
  engine_occupancy     engine counters over the window: decode tokens /
                       (decode steps x slots)
  pool_live_frac       engine counters: pages live / pages held, summed
                       at each step's end
  idle_by_span_s       device idle seconds by the innermost engine.*
                       span over them ("none": outside every span)
  longest_gaps         the ten longest device idle gaps, each named by
                       the innermost engine.* span over its middle
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))

PREFIX = "engine."
STEP = "engine.step"
ADMIT = "engine.admit"
PREFILL_WAIT = "engine.prefill.wait"
WAITS = ("engine.prefill.wait", "engine.decode.wait")
COUNTERS = ("n_decode_steps", "n_decode_tokens", "n_pages_held",
            "n_pages_live")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def rids(self) -> list[int]:
        v = self.stats.get("rids", "")
        return [int(r) for r in str(v).split(";") if r != ""]


def from_planes(planes) -> list[Span]:
    """The `engine.*` events of the host plane, with their stats, by
    start (an enclosing span before the spans it holds)."""
    out = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [Span(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: (s.start_ns, -s.dur_ns))


def steps(spans: list[Span]) -> list[tuple[Span, list[Span]]]:
    """Each engine.step span with the spans that lie inside it."""
    out = [(s, []) for s in spans if s.name == STEP]
    starts = [s.start_ns for s, _ in out]
    for s in spans:
        i = bisect.bisect_right(starts, s.start_ns) - 1
        if (s.name != STEP and i >= 0
                and s.end_ns <= out[i][0].end_ns):
            out[i][1].append(s)
    return out


def host_step_ms(spans: list[Span]) -> Optional[float]:
    """Mean over engine.step spans of (duration - the waits inside), ms."""
    st = steps(spans)
    if not st:
        return None
    host = [s.dur_ns - sum(c.dur_ns for c in inner if c.name in WAITS)
            for s, inner in st]
    return sum(host) / len(host) / 1e6


def idle_intervals(ops) -> list[tuple[float, float]]:
    """Gaps between the union of the op events' intervals (devtrace.Event
    on one chip), in order."""
    gaps, end = [], None
    for e in sorted(ops, key=lambda e: e.start_ns):
        if end is not None and e.start_ns > end:
            gaps.append((end, e.start_ns))
        end = e.end_ns if end is None else max(end, e.end_ns)
    return gaps


def segments(spans: list[Span]) -> list[tuple[float, float, str]]:
    """The host timeline cut where a span starts or ends, each piece named
    by the innermost (shortest) span over it; pieces in no span are left
    out."""
    cuts = sorted({t for s in spans for t in (s.start_ns, s.end_ns)})
    out, active, k = [], [], 0
    order = sorted(spans, key=lambda s: s.start_ns)
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and order[k].start_ns <= a:
            active.append(order[k])
            k += 1
        active = [s for s in active if s.end_ns > a]
        if active:
            out.append((a, b, min(active, key=lambda s: s.dur_ns).name))
    return out


def idle_by_span(ops, spans: list[Span]) -> dict[str, float]:
    """Device idle seconds by the innermost engine.* span over them, and
    under "none" those outside every span."""
    segs = segments(spans)
    out: dict[str, float] = {}
    j = 0
    for a, b in idle_intervals(ops):
        covered = 0.0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(a, segs[k][0]), min(b, segs[k][1])
            if hi > lo:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + (hi - lo) / 1e9
                covered += hi - lo
            k += 1
        out["none"] = out.get("none", 0.0) + (b - a - covered) / 1e9
    return out


def longest_gaps(ops, spans: list[Span], top: int = 10
                 ) -> list[tuple[str, float]]:
    """The `top` longest device idle gaps, each named by the innermost
    engine.* span over its middle ("none" outside every span)."""
    segs = segments(spans)
    starts = [a for a, _, _ in segs]
    out = []
    for a, b in sorted(idle_intervals(ops), key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        name = segs[i][2] if i >= 0 and mid < segs[i][1] else "none"
        out.append((name, (b - a) / 1e9))
    return out


def idle_in_steps_s(ops, spans: list[Span]) -> float:
    """Device idle seconds that lie inside engine.step spans."""
    by = idle_by_span(ops, [s for s in spans if s.name == STEP])
    return by.get(STEP, 0.0)


def first_token_split(spans: list[Span]) -> dict[int, tuple[float, float]]:
    """rid -> (prefill lag, first-token hold) in seconds: from the start of
    the step whose engine.admit names the request to the end of the
    engine.prefill.wait naming it, and from there to the end of that
    wait's step. Requests admitted or answered outside the trace are left
    out."""
    admitted, waited = {}, {}
    for s, inner in steps(spans):
        for c in inner:
            if c.name == ADMIT:
                admitted.update((r, s.start_ns) for r in c.rids())
            elif c.name == PREFILL_WAIT:
                waited.update((r, (c.end_ns, s.end_ns)) for r in c.rids())
    return {r: ((w - admitted[r]) / 1e9, (e - w) / 1e9)
            for r, (w, e) in waited.items() if r in admitted}


def engine_occupancy(d: dict, n_slots: int) -> Optional[float]:
    if not d.get("n_decode_steps"):
        return None
    return d["n_decode_tokens"] / (d["n_decode_steps"] * n_slots)


def pool_live_frac(d: dict) -> Optional[float]:
    if not d.get("n_pages_held"):
        return None
    return d["n_pages_live"] / d["n_pages_held"]


def counters(eng) -> Optional[dict]:
    """The engine's counters these readings take, or None for a program
    that has not got them."""
    if not all(hasattr(eng, k) for k in COUNTERS):
        return None
    return {k: getattr(eng, k) for k in COUNTERS}


def readings(run, spans: list[Span], rid_of: dict, before, after) -> dict:
    """The readings of the module docstring for one traced window."""
    import harness
    import latency

    out: dict = {"spans": len(spans),
                 "steps": sum(s.name == STEP for s in spans)}
    ops = run.trace.ops[sorted(run.trace.ops)[0]] if run.trace.ops else []
    if spans:
        out["host_step_ms"] = host_step_ms(spans)
        out["idle_in_step_frac"] = idle_in_steps_s(ops, spans) / run.window_s
        out["idle_by_span_s"] = idle_by_span(ops, spans)
        out["longest_gaps"] = longest_gaps(ops, spans)
        split = first_token_split(spans)
        lag, hold, resid = [], [], []
        for r in latency.due(run):
            got = split.get(rid_of[id(r)])
            if got is None or r.admitted is None or r.t_first is None:
                continue
            lag.append(got[0])
            hold.append(got[1])
            resid.append((r.t_first - r.arrival)
                         - (r.admitted - r.arrival + got[0] + got[1]))
        if lag:
            out["prefill_lag_p50_ms"] = 1e3 * harness.nearest_rank(lag, 50)
            out["first_token_hold_p50_ms"] = 1e3 * harness.nearest_rank(
                hold, 50)
            out["identity_max_ms"] = 1e3 * max(abs(x) for x in resid)
            out["identity_requests"] = len(resid)
    if before is not None and after is not None:
        d = {k: after[k] - before[k] for k in before}
        out["counters"] = d
        out["engine_occupancy"] = engine_occupancy(d, run.data["n_slots"])
        out["pool_live_frac"] = pool_live_frac(d)
    return out


def traced_run(*, config: dict, workload: dict, seed: int, seconds: float,
               t0: float, peaks: dict):
    """One traced run of a serving cell without its output check, as the
    serving driver makes it. Returns (harness.Run, engine spans, rid of each
    record, counters at the window's open, counters at its close)."""
    import jax
    from jax.profiler import ProfileData

    import devtrace
    import harness
    import traffic

    serve = harness.driver(workload["driver"])
    ref, a, eng = serve.prepare(config, workload, seed)
    spec = workload["traffic"]
    items = traffic.make(spec, seed, seconds, a.vocab)
    tracker = serve.submit(eng, items, True)
    if spec.get("fill_before_window"):
        serve._fill(eng, tracker)
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    compiles = harness.CompileCounter()
    marks = {}

    def opened():
        marks["setup_s"] = time.perf_counter() - t0
        marks["open"] = counters(eng)
        compiles.active = True
        jax.profiler.start_trace(tmp)

    def close():
        jax.profiler.stop_trace()
        compiles.active = False
        marks["close"] = counters(eng)

    drain_s = float(spec.get("drain_seconds", 0))
    open_s, window_end, end_s = serve.window(
        eng, tracker, items, seconds, drain_s, jax.profiler.TraceAnnotation,
        opened, close, start=-traffic.preroll(spec))
    pd = ProfileData.from_file(devtrace.find_xplane(tmp))
    tr = devtrace.from_planes(pd.planes)
    spans = from_planes(pd.planes)
    del pd
    shutil.rmtree(tmp, ignore_errors=True)
    mem = jax.devices()[0].memory_stats() or {}
    recs = [rec for _, rec in tracker.pairs]
    rid_of = {id(rec): req.rid for req, rec in tracker.pairs}
    due = [r for r in recs if 0 <= r.arrival < seconds]
    attempted = due if drain_s > 0 else [r for r in recs
                                         if r.admitted is not None]
    tracker.eng = None
    del eng
    gc.collect()
    run = harness.Run(
        setup_s=marks["setup_s"], window_s=window_end - open_s,
        attempted=len(attempted),
        failed=sum(1 for r in attempted if r.failed), checks=[],
        memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)),
        peaks=peaks,
        data={"records": recs, "tracker": tracker, "reference": ref,
              "arch": a, "seconds": seconds, "drain": drain_s > 0,
              "n_slots": workload["engine"]["n_slots"], "loop_end_s": end_s,
              "compiles_in_window": compiles.count},
        trace=tr)
    return run, spans, rid_of, marks["open"], marks["close"]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=45.0)
    args = p.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
    import harness

    harness.set_env()
    spec = harness.benchmark_spec()
    cell = harness.cell_spec(spec, args.workload)
    workload = harness.load_json("workloads", args.workload)
    config = harness.load_json("configs", cell["config"])
    metrics = (harness.cell_metrics(spec, args.workload, False)
               + harness.cell_metrics(spec, args.workload, True))
    harness.use_compile_cache()
    dev = harness.device(cell["chips"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run, spans, rid_of, before, after = traced_run(
            config=config, workload=workload, seed=seed,
            seconds=args.seconds, t0=t0, peaks=harness.peaks(dev["kind"]))
        res = harness.report(run, metrics, dev, True)
        for k in ("correct", "checks"):     # no output check was made
            res.pop(k)
        res["compiles_in_window"] = run.data["compiles_in_window"]
        res["spans"] = readings(run, spans, rid_of, before, after)
        res["seed"] = seed
        print("hostspans " + json.dumps(res), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
