"""Deterministic fault injection for the continuous-batching engine.

Chaos testing is only useful when a failing run can be replayed exactly:
every fault here is pinned to an engine *step index* (``step()`` call
count, which under the virtual clock is a pure function of the trace), so
a `FaultPlan` turns "the server fell over under load" into a seeded,
step-indexed schedule that reproduces bit-for-bit on every machine. The
engine consumes the plan inside ``step()`` — see
``ContinuousEngine._apply_faults`` — and each fault kind exercises one
graceful-degradation path:

  nan_logits      poison one slot's decode logits with NaN; the isfinite
                  sentinel in the decode scan must quarantine that slot
                  (retire with ``error``, free pages) without perturbing
                  co-batched slots' tokens.
  pool_exhaust    pin free pages for a few steps so admission sees a full
                  pool; scheduling must degrade (queue/preempt), never
                  crash, and the pages come back on schedule.
  step_exception  raise ``FaultInjected`` out of ``step()`` — a simulated
                  process crash. ``run_resilient`` below rebuilds the
                  engine and resumes from the last snapshot.
  spill_corrupt   flip bytes in the next spill snapshot's host payload;
                  the checksum taken at spill time must catch it on
                  restore and quarantine the request instead of resuming
                  a stream on garbage KV.
  latency_spike   jump the virtual clock, aging every queued request at
                  once (deadline shedding and aging promotion both fire).
  kernel_fault    fail the next fused decode dispatch with
                  ``KernelFaultInjected``; the engine must fall back
                  fused -> gather paged attention and keep the token
                  stream identical. It is the only failure the engine
                  answers that way: a real lowering error propagates.

The driver (`run_resilient`) owns the plan across crashes: a
`step_exception` that fired is dropped from the plan handed to the
rebuilt engine — exactly like a real crash, which does not repeat just
because the process restarted — while every other fault kind stays and
re-fires deterministically when the restored engine replays its steps.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import numpy as np

FAULT_KINDS = ("nan_logits", "pool_exhaust", "step_exception",
               "spill_corrupt", "latency_spike", "kernel_fault")


class FaultInjected(RuntimeError):
    """An injected step_exception — the simulated process crash."""

    def __init__(self, fault: "Fault"):
        super().__init__(f"injected fault: {fault}")
        self.fault = fault


class KernelFaultInjected(RuntimeError):
    """An injected kernel_fault — the simulated fused-dispatch failure the
    engine answers by degrading to the gather read. Only this exception
    triggers that fallback: a real lowering error propagates."""


@dataclasses.dataclass(frozen=True)
class Fault:
    """One scheduled failure.

    `step` is the engine step index (``n_steps_total``) at which it fires.
    `slot` targets nan_logits (-1 = slot 0 at fire time); `pages` and
    `duration` parameterize pool_exhaust (pages pinned, steps held) and
    latency_spike (virtual-time jump)."""

    step: int
    kind: str
    slot: int = -1
    pages: int = 0
    duration: int = 0

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"one of {FAULT_KINDS}")


class FaultPlan:
    """An immutable, deterministically-ordered fault schedule."""

    def __init__(self, faults: Sequence[Fault] = (),
                 seed: Optional[int] = None):
        self.faults = tuple(sorted(
            faults, key=lambda f: (f.step, FAULT_KINDS.index(f.kind),
                                   f.slot, f.pages, f.duration)))
        self.seed = seed

    def __len__(self) -> int:
        return len(self.faults)

    def __iter__(self):
        return iter(self.faults)

    def __repr__(self) -> str:
        return f"FaultPlan(seed={self.seed}, n={len(self.faults)})"

    def at(self, step: int) -> list[Fault]:
        """Faults scheduled for engine step `step`, in canonical order."""
        return [f for f in self.faults if f.step == step]

    def drop(self, fault: Fault) -> "FaultPlan":
        """A new plan without `fault` (one occurrence) — how the crash
        driver retires a step_exception that already fired."""
        rest = list(self.faults)
        rest.remove(fault)
        return FaultPlan(rest, seed=self.seed)

    @classmethod
    def seeded(cls, seed: int, *, n_steps: int = 64, n_slots: int = 8,
               n_faults: int = 6,
               kinds: Sequence[str] = ("nan_logits", "pool_exhaust",
                                       "latency_spike", "kernel_fault",
                                       "spill_corrupt"),
               crashes: int = 0) -> "FaultPlan":
        """Draw a reproducible schedule: `n_faults` failures of the given
        kinds over the first `n_steps` engine steps, plus `crashes`
        step_exceptions (separate knob — they need a crash-recovery driver,
        so plain replay callers get none by default)."""
        rng = np.random.default_rng(seed)
        faults = []
        for _ in range(n_faults):
            kind = str(rng.choice(list(kinds)))
            faults.append(Fault(
                step=int(rng.integers(1, max(2, n_steps))), kind=kind,
                slot=int(rng.integers(0, n_slots)),
                pages=int(rng.integers(1, 9)),
                duration=int(rng.integers(1, 5))))
        for _ in range(crashes):
            faults.append(Fault(step=int(rng.integers(1, max(2, n_steps))),
                                kind="step_exception"))
        return cls(faults, seed=seed)


def run_resilient(build_engine: Callable[[], object], trace, *,
                  faults: Optional[FaultPlan] = None,
                  snapshot_every: int = 8, store_dir: Optional[str] = None,
                  max_steps: int = 200_000) -> dict:
    """Crash-tolerant trace replay: snapshot periodically, and when a step
    raises `FaultInjected` (the simulated crash), rebuild the engine from
    scratch and restore the last snapshot — in-flight work replays from
    the checkpoint with bit-identical tokens.

    `build_engine` must construct a fresh engine identical to the one that
    crashed (same params/config/geometry); `store_dir`, when given, routes
    every snapshot through ``checkpoint.store.save_snapshot`` /
    ``load_snapshot`` so the disk round trip is exercised too. Returns the
    traffic report plus crash/snapshot accounting."""
    from repro.serve.traffic import summarize

    plan = faults if faults is not None else FaultPlan()
    eng = build_engine()
    eng.faults = plan
    for it in trace:
        eng.submit(it.prompt, max_new=it.max_new, arrival=it.arrival,
                   priority=it.priority,
                   deadline=getattr(it, "deadline", None))
    snap = eng.snapshot()      # step-0 checkpoint: a crash before the
    #                            first periodic snapshot is still recoverable
    n_crashes = n_snapshots = steps = 0
    while not eng.sched.all_done():
        if steps >= max_steps:
            raise RuntimeError(f"resilient loop exceeded {max_steps} steps")
        steps += 1
        try:
            eng.step(float(eng.t))
            eng.t += 1
        except FaultInjected as e:
            plan = plan.drop(e.fault)
            n_crashes += 1
            eng = build_engine()
            eng.faults = plan
            eng.restore(snap)
            continue
        if snapshot_every and steps % snapshot_every == 0:
            snap = eng.snapshot()
            if store_dir is not None:
                from repro.checkpoint.store import (load_snapshot,
                                                    save_snapshot)
                save_snapshot(store_dir, snap)
                snap = load_snapshot(store_dir)
            n_snapshots += 1
    done = sorted(eng.sched.drain_finished(), key=lambda r: r.rid)
    report = summarize(done)
    report["scheduler"] = eng.sched.stats()
    report["spill"] = {"spilled_pages": eng.n_spilled_pages,
                       "restored_pages": eng.n_restored_pages}
    report["faults"] = eng.fault_stats()
    # `done` (not the objects submit returned) is authoritative: after a
    # crash the restored engine rebuilt its Request objects from the
    # snapshot, so pre-crash handles go stale
    report["requests"] = done
    return {"engine": eng, "report": report, "requests": done,
            "n_crashes": n_crashes, "n_snapshots": n_snapshots}
