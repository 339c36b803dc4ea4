"""Serving driver: the program's `ContinuousEngine` under an open loop.

Set-up draws the weights on the chip straight into the served format
(bench/weights.py), builds the engine with the workload's settings, and
warms every shape the traffic can reach through the engine's own
`submit`/`step` (one request at a time, chosen by `warm_requests`). The
loop then submits the seeded requests at their scheduled arrivals and
calls `step(now)`; each token is stamped by the benchmark's clock after
the `step()` that produced it returns (the engine blocks on the tokens
before returning). With a pre-roll, the loop starts that long before the
window, so the window opens on a steady population; the pre-roll is
set-up. The window holds the steps that start in [0, `--seconds`). With
`drain_seconds`, the requests due in the window are then followed for up
to that long. A run in which anything compiles inside the window exits
with an error and no result. Afterwards the peak device memory is read,
the program's state is freed, and a sample of the served requests is
compared with the plain reference (bench/check.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import shutil
import tempfile
import time
from typing import Optional

import numpy as np

import check
import harness
import traffic
import weights


@dataclasses.dataclass
class Record:
    """One request as the benchmark's clock saw it (seconds from the
    window's start)."""

    arrival: float
    n_prompt: int
    max_new: int
    admitted: Optional[float] = None
    t_first: Optional[float] = None   # first stamp inside the window
    n_first: int = 1                  # tokens served at that stamp
    t_last: Optional[float] = None
    n_tokens: int = 0
    failed: bool = False


def program_config(config: dict):
    prog = config["program"]
    cfg = importlib.import_module(prog["module"]).CONFIG
    return cfg.replace(**prog.get("overrides", {}))


# ------------------------------------------------------------ warm-up

def _chunks(eng, n_prompt: int):
    """(bucket, has context, read width) of each prefill call a prompt of
    n_prompt tokens makes (one chunk per step, `chunked_prefill` long)."""
    start = 0
    while start < n_prompt:
        end = n_prompt
        if eng.chunk_tokens:
            end = min(n_prompt, start + eng.chunk_tokens)
        yield (eng._bucket(end - start), start > 0,
               eng._read_width(end) if start > 0 else 0)
        start = end


def _decode_blocks(eng, n_prompt: int, max_new: int) -> set:
    """(steps, read width) of each decode block of a request running
    alone: blocks of `decode_block` steps, then the binary digits of the
    rest, largest first (the engine's rule for landing on max_new)."""
    c, r, out = n_prompt, max_new - 1, set()
    while r > 0:
        k = min(eng.decode_block, 1 << (r.bit_length() - 1))
        out.add((k, eng._read_width(c + k)))
        c, r = c + k, r - k
    return out


def warm_requests(eng, lens) -> list[tuple[int, int]]:
    """(prompt length, max_new) of warm-up requests that, run one at a
    time, make every prefill call shape a prompt in [pmin, pmax] can make
    and every decode block shape (pow2 steps up to `decode_block`, read
    width) that some slot depth of the traffic can reach."""
    pmin, pmax, _, omax = lens
    out, seen = [], set()
    for n in range(pmin, pmax + 1):
        sig = set(_chunks(eng, n))
        if not sig <= seen:
            out.append((n, 1))
            seen |= sig
    big = eng.decode_block
    ks = [1 << i for i in range(big.bit_length()) if 1 << i <= big]
    todo = {(k, eng._read_width(c + k)) for k in ks
            for c in range(pmin, pmax + omax - k + 1)}
    # first one request per width whose tail blocks (big/2, ..., 1)
    # follow a block of `big` steps, all at that width
    for w in sorted({w for _, w in todo}):
        for p in range(pmin, pmax + 1):
            n = 2 * big
            if _decode_blocks(eng, p, n) == {(k, w) for k in ks}:
                out.append((p, n))
                todo -= _decode_blocks(eng, p, n)
                break
    # then one request per block shape still missing: `k` steps at depth
    # c, after decode blocks of `big` steps past the longest prompt
    for k, w in sorted(todo):
        for c in range(pmin, pmax + omax - k + 1):
            q = max(0, -(-(c - pmax) // big))
            if eng._read_width(c + k) != w or c - q * big < pmin:
                continue
            p, n = c - q * big, q * big + k + 1
            if n <= omax and (k, w) in _decode_blocks(eng, p, n):
                out.append((p, n))
                break
        else:
            raise ValueError(f"no warm-up request reaches a decode block "
                             f"of {k} steps at width {w}")
    return out


def warm(eng, lens, vocab: int) -> None:
    rng = np.random.default_rng(0)
    for n_prompt, max_new in warm_requests(eng, lens):
        eng.submit(rng.integers(0, vocab, n_prompt), max_new=max_new)
        while not eng.sched.all_done():
            eng.step(0.0)
    eng.sched.drain_finished()


# ------------------------------------------------------------- window

class _Tracker:
    """Host-side counts of the window: stamps of each request's tokens,
    and with `detail` the decode steps, the context of every decode
    token, the prefill chunks and the KV pages held and live."""

    def __init__(self, eng, detail: bool):
        self.eng = eng
        self.detail = detail
        self.pairs: list[tuple] = []       # (request, record), item order
        self.live: dict[int, tuple] = {}   # rid -> (request, record)
        self.tokens_in_window = 0
        self.decode_steps = 0          # sum of decode block lengths
        self.decode_tokens = 0
        self.kv_live = 0               # pages holding live tokens, summed
        self.kv_reserved = 0           # pages held by slots, summed
        self.prefill_chunks = []       # (start, end, bucket) per call
        self.first_tokens = 0          # prompts completed in the window
        self.decode_ctx = []           # KV length that made each token

    def submit(self, req, rec):
        self.pairs.append((req, rec))
        self.live[req.rid] = (req, rec)

    def before(self):
        eng = self.eng
        self._steps0 = eng.n_decode_steps
        if self.detail:
            self._slots0 = list(eng.sched.slots)
            self._len0 = eng.cur_len.copy()
            self._act0 = eng.active.copy()

    def after(self, t: float, in_window: bool):
        k = self.eng.n_decode_steps - self._steps0
        new_decode = 0
        done = []
        for rid, (req, rec) in self.live.items():
            n = len(req.tokens)
            if rec.admitted is None and req.admitted_at is not None:
                rec.admitted = req.admitted_at
            if n > rec.n_tokens:
                first = max(rec.n_tokens, 1)
                if rec.t_first is None:
                    rec.t_first, rec.n_first = t, n
                if rec.n_tokens == 0:
                    self.first_tokens += in_window
                new_decode += n - first
                if in_window:
                    self.tokens_in_window += n - rec.n_tokens
                    if self.detail:
                        # token i >= 1 is decoded with prompt + i tokens
                        # in the cache
                        self.decode_ctx.extend(rec.n_prompt + i
                                               for i in range(first, n))
                rec.t_last = t
                rec.n_tokens = n
            if req.done:
                rec.failed = bool(req.rejected or req.error or req.shed
                                  or req.cancelled or n < req.max_new)
                done.append(rid)
        for rid in done:
            del self.live[rid]
        if not in_window:
            return
        self.decode_steps += k
        self.decode_tokens += new_decode
        if self.detail:
            self._pages_and_chunks()

    def _pages_and_chunks(self):
        eng = self.eng
        page = eng.spec.page_size
        for s, req in enumerate(eng.sched.slots):
            if req is None:
                continue
            self.kv_reserved += int((eng.pool.tables[s] >= 0).sum())
            self.kv_live += -(-int(eng.cur_len[s]) // page)
            if self._act0[s]:
                continue
            # one prefill chunk per step per prefilling slot
            start = int(self._len0[s]) if self._slots0[s] is req else 0
            end = min(int(eng.cur_len[s]), req.n_prompt)
            if end > start:
                self.prefill_chunks.append(
                    (start, end, eng._bucket(end - start)))


def _fill(eng, tracker) -> None:
    """Step until every request of the first admission wave has its first
    token (requests due at t = 0 only): a backlog then starts the window
    with its slots decoding, instead of in its first wave of prefills.
    Tokens served here are set-up, not window."""
    eng.step(0.0)
    wave = [r for r in eng.sched.slots if r is not None]
    while not all(r.tokens for r in wave):
        eng.step(0.0)
    for req, rec in tracker.pairs:
        rec.n_tokens = len(req.tokens)
        if req.admitted_at is not None:
            rec.admitted = req.admitted_at


def submit(eng, items, detail: bool,
           deadline: Optional[float] = None) -> _Tracker:
    """Hand every request to the engine at its scheduled arrival (the
    engine admits none before its time) and start tracking it. With
    `deadline`, the engine cancels whatever still runs past it."""
    tracker = _Tracker(eng, detail)
    for it in items:
        tracker.submit(eng.submit(it.prompt, max_new=it.max_new,
                                  arrival=it.arrival, deadline=deadline),
                       Record(it.arrival, len(it.prompt), it.max_new))
    return tracker


def window(eng, tracker, items, seconds: float, drain_s: float, annotate,
           on_open, on_close, start: float = 0.0):
    """Run the open loop from `start` (<= 0) on the window's clock.
    `on_open()` runs before the first step that starts at 0 or later,
    `on_close()` once the window has closed, before the drain. Returns
    (start of the window's first step, end of its last step, end of the
    loop) in seconds on the window's clock."""
    arrivals = [it.arrival for it in items]
    clock = time.perf_counter
    t0 = clock() - start
    open_s = window_end = None
    while True:
        now = clock() - t0
        if open_s is None and now >= 0:
            on_open()
            open_s = now = clock() - t0
        in_window = open_s is not None and now < seconds
        if open_s is not None and not in_window and window_end is None:
            window_end = now
            on_close()
            if drain_s <= 0:
                break
        if window_end is not None and (not tracker.live
                                       or now > seconds + drain_s):
            break
        tracker.before()
        with annotate("bench.step"):
            did = eng.step(now)
        tracker.after(clock() - t0, in_window)
        if not did:
            nxt = [a for a in arrivals if a > now]
            with annotate("bench.wait"):
                time.sleep(max(0.0, min(nxt[0] - now if nxt else 1e-3,
                                        1e-3)))
    return open_s, window_end, now


# ------------------------------------------------------------ driver

def prepare(config: dict, workload: dict, seed: int):
    """Weights, engine and warm-up: the set-up of a serving cell.
    Returns (reference module, its description of the model, engine)."""
    import jax

    from repro.serve.engine import ContinuousEngine

    ref = harness.reference(config)
    a = ref.arch(config)
    cfg = program_config(config)
    lens = traffic.length_range(workload["traffic"])
    eng = ContinuousEngine(cfg, weights.build_served(ref, a, cfg, seed),
                           max_len=lens[1] + lens[3], **workload["engine"])
    warm(eng, lens, a.vocab)
    jax.block_until_ready(eng.cache)
    return ref, a, eng


def run(*, config: dict, workload: dict, seed: int, seconds: float,
        trace: bool, t0: float, peaks: dict,
        control: Optional[str] = None) -> harness.Run:
    """One run of a serving cell. With `control` (a dtype name), the
    output check compares the plain reference computed in that precision,
    put in the program's place, instead of the program's tokens."""
    import jax

    from repro.analysis import sanitize

    ref, a, eng = prepare(config, workload, seed)
    spec = workload["traffic"]
    items = traffic.make(spec, seed, seconds, a.vocab)
    tracker = submit(eng, items, trace)
    if spec.get("fill_before_window"):
        _fill(eng, tracker)
    counter = harness.CompileCounter()
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        annotate = jax.profiler.TraceAnnotation
    else:
        annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    marks = {}

    def opened():
        marks["setup_s"] = time.perf_counter() - t0
        marks["traces0"] = sum(sanitize.trace_counts().values())
        counter.active = True
        if trace:
            jax.profiler.start_trace(tmp)

    def close():
        counter.active = False
        marks["traces"] = (sum(sanitize.trace_counts().values())
                           - marks["traces0"])
        if trace:
            jax.profiler.stop_trace()

    drain_s = float(spec.get("drain_seconds", 0))
    open_s, window_end, end_s = window(eng, tracker, items, seconds, drain_s,
                                       annotate, opened, close,
                                       start=-traffic.preroll(spec))
    tr = None
    if trace:
        import devtrace
        tr = devtrace.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
    window_s = window_end - open_s
    print(f"bench: window {window_s:.3f} s, {tracker.decode_steps} decode "
          f"steps, {tracker.tokens_in_window} tokens; {counter.count} "
          f"backend compiles and {marks['traces']} engine jit traces "
          f"inside it", flush=True)
    if counter.count or marks["traces"]:
        raise SystemExit("bench: programs compiled inside the window; the "
                         "warm-up misses a shape the traffic uses")
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))

    recs = [rec for _, rec in tracker.pairs]
    # every served token is final under greedy decoding, so requests the
    # loop left running are compared as far as they were served
    served = [check.Served(req.prompt, list(req.tokens))
              for req, rec in tracker.pairs if req.tokens and not rec.failed]
    # free the program's weights and KV pools before the reference runs
    tracker.eng = None
    del eng
    gc.collect()

    cc = workload["check"]
    reading = check.served_gaps(ref, a, seed,
                                check.sample(served, seed, cc["requests"]),
                                control=control)
    print(f"bench: check readings {reading}", flush=True)
    key = cc["compare"]
    gap = reading["control_" + key if control else key]
    checks = [harness.Check(key, gap, cc["limit"], gap <= cc["limit"]),
              harness.Check("served_positions", reading["positions"],
                            cc["min_positions"],
                            reading["positions"] >= cc["min_positions"])]
    drain = drain_s > 0
    due = [r for r in recs if 0 <= r.arrival < seconds]
    started = [r for r in recs if r.admitted is not None]
    attempted = due if drain else started
    failed = sum(1 for r in attempted if r.failed)
    return harness.Run(
        setup_s=marks["setup_s"], window_s=window_s,
        attempted=len(attempted), failed=failed, checks=checks,
        memory_peak_bytes=peak, peaks=peaks,
        data={"records": recs, "tracker": tracker, "reference": ref,
              "arch": a, "seconds": seconds, "drain": drain,
              "n_slots": workload["engine"]["n_slots"],
              "loop_end_s": end_s, "readings": reading},
        trace=tr)
