"""Reduction of a JAX profiler trace to device busy time, per-op and
per-program device time, and idle gaps labelled by the host's spans.

A TPU trace (`jax.profiler.start_trace`) holds one plane per chip,
`/device:TPU:<i>`, whose line "XLA Ops" has one event per operation run
on the chip and whose line "XLA Modules" has one event per program run;
the host plane `/host:CPU` holds the benchmark's own spans (names that
start with "bench."). Every event has a start and a duration in
nanoseconds on one clock.

    python bench/devtrace.py <dir or .xplane.pb>   # describe a trace by hand
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]      # device plane -> op events
    modules: dict[str, list[Event]]  # device plane -> program events
    spans: list[Event]               # the benchmark's host spans


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns, e.duration_ns) for e in line.events]


def from_planes(planes) -> Trace:
    """Build a Trace from ProfileData-like planes (name, lines; each line
    has name and events with name, start_ns, duration_ns)."""
    ops, modules, spans = {}, {}, []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, modules, sorted(spans, key=lambda e: e.start_ns))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_planes(ProfileData.from_file(find_xplane(path)).planes)


def union_ns(events: list[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is None or e.start_ns >= end:
            total += e.dur_ns
            end = e.end_ns
        elif e.end_ns > end:
            total += e.end_ns - end
            end = e.end_ns
    return total


def busy_seconds(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not tr.ops:
        return 0.0
    return sum(union_ns(ev) for ev in tr.ops.values()) / len(tr.ops) / 1e9


# control-flow ops span the ops of their bodies, which are events too
NESTING = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    """An op event's HLO instruction name without its instance number:
    '%dequant_matmul_pallas.69 = f32[...] custom-call(...)' ->
    'dequant_matmul_pallas'."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def op_seconds(tr: Trace) -> dict[str, float]:
    """Device seconds by op kind, summed over chips; control-flow ops,
    whose time their bodies' ops already count, are left out."""
    out: dict[str, float] = defaultdict(float)
    for ev in tr.ops.values():
        for e in ev:
            kind = op_kind(e.name)
            if kind.split(".")[0] not in NESTING:
                out[kind] += e.dur_ns / 1e9
    return dict(out)


def _total(src: dict[str, list[Event]], keep) -> tuple[int, float]:
    n, s = 0, 0.0
    for ev in src.values():
        for e in ev:
            if keep(e.name):
                n += 1
                s += e.dur_ns / 1e9
    return n, s


def kernel(tr: Trace, name: str) -> tuple[int, float]:
    """(count, device seconds) of the op events of the kernel `name`
    (their `op_kind`, compared whole), over all chips."""
    return _total(tr.ops, lambda op: op_kind(op) == name)


def programs(tr: Trace, needle: str) -> tuple[int, float]:
    """(count, device seconds) of the program events whose name contains
    `needle`, over all chips."""
    return _total(tr.modules, lambda prog: needle in prog)


def idle_gaps(tr: Trace, top: int = 10) -> list[tuple[str, float]]:
    """The `top` longest gaps between device operations on the first
    chip, each named by the innermost benchmark span that covers its
    middle ("none" where the host was in no span)."""
    if not tr.ops:
        return []
    ev = sorted(tr.ops[sorted(tr.ops)[0]], key=lambda e: e.start_ns)
    gaps, end = [], None
    for e in ev:
        if end is not None and e.start_ns > end:
            gaps.append((e.start_ns - end, end, e.start_ns))
        end = e.end_ns if end is None else max(end, e.end_ns)
    gaps.sort(reverse=True)
    out = []
    for dur, a, b in gaps[:top]:
        mid = (a + b) / 2
        cover = [s for s in tr.spans if s.start_ns <= mid <= s.end_ns]
        name = min(cover, key=lambda s: s.dur_ns).name if cover else "none"
        out.append((name, dur / 1e9))
    return out


def top_ops(tr: Trace, top: int = 10) -> list[tuple[str, float]]:
    return sorted(op_seconds(tr).items(), key=lambda kv: -kv[1])[:top]


def describe(path: str, n: int = 40) -> str:
    """Planes, lines and the busiest event names of a trace: what to read
    before writing a reduction against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    lines = []
    for plane in pd.planes:
        lines.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  line {line.name!r}: {len(evs)} events")
            tot: dict[str, list] = defaultdict(lambda: [0, 0.0])
            for e in evs:
                tot[e.name][0] += 1
                tot[e.name][1] += e.duration_ns / 1e6
            for name, (c, ms) in sorted(tot.items(),
                                        key=lambda kv: -kv[1][1])[:n]:
                lines.append(f"    {ms:12.3f} ms {c:8d}x  {name[:160]}")
            if plane.name.startswith(DEVICE_PREFIX) and evs:
                e = evs[len(evs) // 2]
                stats = {k: str(v)[:200] for k, v in e.stats}
                lines.append(f"    sample {e.name!r} stats {stats}")
    return "\n".join(lines)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
