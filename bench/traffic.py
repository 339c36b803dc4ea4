"""Seeded request traffic, made from the parameters of a workload file.

Adapted from the program's `serve/traffic.py` (`make_trace`: a seeded
Poisson open loop), with these changes:

  * lengths follow a lognormal law clipped to [min, max], the heavy tail
    of real prompts and answers;
  * the set of (prompt, output) lengths and the set of gaps between
    arrivals are drawn once from the traffic's own `shape_seed`, for the
    pre-roll and for the window apart, so every seed offers the same work
    in each. With `shuffle`, the run's seed permutes the
    order of both, so each seed sends another schedule; without it, every
    seed sends them in the drawn order. The run's seed draws the token
    ids (and the driver draws the weights from it);
  * with `preroll_seconds`, arrivals start that long before the window
    (at negative times), so the window opens on a system that already
    serves a steady population of requests.

Arrival kinds: "poisson" (exponential gaps, scaled so that exactly
round(rate * span) requests arrive in each span, the pre-roll and the
window), "backlog" (`n` requests all due at t = 0).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Item:
    arrival: float          # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int


def _lengths(law: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if law["law"] != "lognormal":
        raise ValueError(f"unknown length law {law['law']!r}")
    x = np.rint(law["median"] * np.exp(law["sigma"] * rng.standard_normal(n)))
    return np.clip(x, int(law["min"]), int(law["max"])).astype(np.int64)


def preroll(spec: dict) -> float:
    return float(spec.get("preroll_seconds", 0))


def _spans(spec: dict, seconds: float) -> list[tuple[float, float, int]]:
    """(start, length, requests) of the pre-roll and of the window."""
    arr = spec["arrivals"]
    if arr["kind"] == "backlog":
        return [(0.0, 0.0, int(arr["n"]))]
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    spans = [(-preroll(spec), preroll(spec)), (0.0, float(seconds))]
    return [(t, span, int(round(arr["rate"] * span)))
            for t, span in spans if span > 0]


def count(spec: dict, seconds: float) -> int:
    """Requests the traffic offers in its pre-roll and a window of
    `seconds`."""
    return sum(n for _, _, n in _spans(spec, seconds))


def length_range(spec: dict) -> tuple[int, int, int, int]:
    """(min prompt, max prompt, min output, max output) any seed can draw."""
    p, o = spec["prompt"], spec["output"]
    return int(p["min"]), int(p["max"]), int(o["min"]), int(o["max"])


def make(spec: dict, seed: int, seconds: float, vocab: int) -> list[Item]:
    """The requests of one run, sorted by arrival; those of the pre-roll
    arrive before 0. The pre-roll and the window each get their own
    count, lengths and gaps, so a shuffle moves no work from one into the
    other."""
    shape = np.random.default_rng(int(spec["shape_seed"]))
    rng = np.random.default_rng(int(seed))
    arrivals, plens, olens = [], [], []
    for start, span, n in _spans(spec, seconds):
        p = _lengths(spec["prompt"], n, shape)
        o = _lengths(spec["output"], n, shape)
        # n - 1 gaps between the n arrivals, out of n + 1 that fill the
        # span: the first arrival is at its start, the last before its end
        g = shape.exponential(1.0, n + 1)
        gaps = (g * span / g.sum())[1:n]
        if spec.get("shuffle"):
            order = rng.permutation(n)
            p, o = p[order], o[order]
            gaps = gaps[rng.permutation(n - 1)]
        arrivals.append(start + np.concatenate([[0.0], np.cumsum(gaps)]))
        plens.append(p)
        olens.append(o)
    arrivals, plens, olens = (np.concatenate(x)
                              for x in (arrivals, plens, olens))
    return [Item(float(arrivals[i]),
                 rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                 int(olens[i]))
            for i in range(len(arrivals))]
