"""Named host spans of the serving step, written into the JAX profiler's
own trace.

A span is a `jax.profiler.TraceAnnotation`. While a profiler session
records (`jax.profiler.trace` / `start_trace`), each span lands on the
host plane `/host:CPU` with its attributes as event stats, on the same
clock as the device's op events, so a device idle gap can be named by the
host work around it. With no session a span costs one TraceMe
construction (about a microsecond); there is no other switch. Attribute
values that cost more than a field read are built only while a session
records (`ids()`); those known only at a span's end are set there with
`set_metadata`.

This module reads no clock, and nothing in the program reads a span back:
spans never feed scheduling, so the virtual-clock replay is unchanged.

Spans of one `ContinuousEngine.step()` (serve/engine.py), nested as shown:

    engine.step           the whole step                   step
      engine.admit        faults, deadlines, admission,    rids first admitted
                          restores
      engine.prefill      one prefill call: host arrays    rows, bucket, ctx,
                          and dispatch                     width
      engine.prefill.wait fetch of the sampled first       rids whose prompt
                          tokens; ends when they are on    completed
                          the host
      engine.decode       decode block (or speculative     k, slots, width
                          round): host part and dispatch
      engine.decode.wait  fetch of the block's tokens
      engine.emit         per-token emit, retire and       tokens
                          quarantine loop
"""
from __future__ import annotations

from typing import Iterable

from jax.profiler import TraceAnnotation

STEP = "engine.step"
ADMIT = "engine.admit"
PREFILL = "engine.prefill"
PREFILL_WAIT = "engine.prefill.wait"
DECODE = "engine.decode"
DECODE_WAIT = "engine.decode.wait"
EMIT = "engine.emit"

NAMES = (STEP, ADMIT, PREFILL, PREFILL_WAIT, DECODE, DECODE_WAIT, EMIT)


def span(name: str, **attrs) -> TraceAnnotation:
    """A context manager that records `name` with `attrs` as its stats
    while a profiler session records, and does nothing else."""
    return TraceAnnotation(name, **attrs)


def ids(values: Iterable[int]) -> str:
    """A list attribute: the values joined by ';' (a ',' would split the
    trace's metadata, which keeps only the part before it), or "" without
    consuming `values` when no session records."""
    if not TraceAnnotation.is_enabled():
        return ""
    return ";".join(str(int(v)) for v in values)
