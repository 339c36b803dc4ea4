"""Host spans of ContinuousEngine.step read back from a profiler trace,
the step-end counters of decode tokens and KV pages, and token stamps
under a wall clock taken when the step that made a token has returned."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import spans
from repro.configs import TINY
from repro.models.transformer import init_lm
from repro.serve.engine import ContinuousEngine

CFG = TINY.replace(n_repeats=2, d_model=64, head_dim=16, d_ff=128)
# three prompts, two of them longer than one 16-token chunk; requests
# 0-2 arrive at once, request 3 one step later
PROMPTS = [(8, 6), (24, 5), (40, 7), (8, 4)]


@pytest.fixture(scope="module")
def tiny_lm():
    return init_lm(CFG, jax.random.PRNGKey(0))


def _engine(params):
    return ContinuousEngine(CFG, params, n_slots=4, max_len=64,
                            page_size=8, prefill_bucket=16,
                            chunked_prefill=16, decode_block=4)


def _submit(eng):
    rng = np.random.default_rng(3)
    return [eng.submit(rng.integers(0, CFG.vocab_size, n), max_new=m,
                       arrival=float(i == 3))
            for i, (n, m) in enumerate(PROMPTS)]


def _serve(eng):
    reqs = _submit(eng)
    t = 0
    while not eng.sched.all_done():
        eng.step(float(t))
        t += 1
    return reqs


def _host_spans(trace_dir):
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name in spans.NAMES]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_step_spans_nest_and_name_their_requests(tiny_lm, tmp_path):
    plain = [r.tokens for r in _serve(_engine(tiny_lm))]
    eng = _engine(tiny_lm)
    with jax.profiler.trace(str(tmp_path)):
        reqs = _serve(eng)
    # spans change nothing the engine computes
    assert [r.tokens for r in reqs] == plain

    got = _host_spans(str(tmp_path))
    steps = [s for s in got if s[0] == spans.STEP]
    assert [s[3]["step"] for s in steps] == list(range(eng.n_steps_total))
    inner = {}
    for name, a, b, stats in got:
        if name == spans.STEP:
            continue
        # every other span lies inside exactly one step
        owner = [s for s in steps if s[1] <= a and b <= s[2]]
        assert len(owner) == 1, name
        inner.setdefault(owner[0][3]["step"], []).append((name, stats))
    assert all(inner[s][0][0] == spans.ADMIT for s in inner)

    def ids(name):
        return [stats["rids"] for ss in inner.values() for n, stats in ss
                if n == name and "rids" in stats]

    # a list of ids is joined by ';' and every request is named once as
    # admitted and once when its first token reached the host
    assert ids(spans.ADMIT)[:2] == ["0;1;2", 3]
    first = [str(r) for v in ids(spans.PREFILL_WAIT)
             for r in str(v).split(";")]
    assert sorted(first) == ["0", "1", "2", "3"]
    prefills = [st for ss in inner.values() for n, st in ss
                if n == spans.PREFILL]
    assert {(p["bucket"], p["ctx"]) for p in prefills} == {(16, 0), (16, 1)}
    assert len(prefills) == eng.n_prefills
    decodes = [st for ss in inner.values() for n, st in ss
               if n == spans.DECODE]
    assert sum(d["k"] for d in decodes) == eng.n_decode_steps
    assert all(1 <= d["slots"] <= 4 and d["width"] >= 1 for d in decodes)
    waits = sum(n == spans.DECODE_WAIT for ss in inner.values()
                for n, _ in ss)
    assert waits == len(decodes)
    emitted = sum(st["tokens"] for ss in inner.values() for n, st in ss
                  if n == spans.EMIT)
    assert emitted == eng.n_decode_tokens == sum(len(r.tokens) - 1
                                                 for r in reqs)


def test_step_end_counters_sum_pages_held_and_live(tiny_lm):
    eng = _engine(tiny_lm)
    _submit(eng)
    held = live = t = 0
    while not eng.sched.all_done():
        eng.step(float(t))
        t += 1
        for s, req in enumerate(eng.sched.slots):
            if req is not None:
                held += int((eng.pool.tables[s] >= 0).sum())
                live += -(-int(eng.cur_len[s]) // eng.spec.page_size)
        assert (eng.n_pages_held, eng.n_pages_live) == (held, live)
    assert 0 < live < held
    assert eng.snapshot()["counters"]["n_pages_live"] == live


def test_wall_clock_stamps_a_token_when_its_step_returns(tiny_lm):
    eng = _engine(tiny_lm)
    reqs = _submit(eng)
    readings = iter(range(10_000))
    passed = []
    step = eng.step

    def recorded(now):
        passed.append(now)
        return step(now)

    eng.step = recorded
    eng.run(clock=lambda: float(next(readings)))
    for r in reqs:
        # admission is stamped with the reading passed to its step; a
        # token's stamps with the reading taken after its step returned
        assert r.admitted_at in passed
        assert r.first_token_at not in passed
        assert r.first_token_at - 1 in passed
        assert r.finished_at not in passed and r.finished_at - 1 in passed
        assert r.finished_at >= r.first_token_at > r.admitted_at
