"""Fault tolerance: engine snapshot/restore, deterministic fault
injection, and graceful degradation.

Covers snapshot -> kill -> restore token identity across the attention
zoo (including the disk round trip through checkpoint.store), fingerprint
refusal of mismatched configs, every fault kind's degradation path
(nan-logit quarantine shielding co-batched slots, pool-exhaust holds that
release on schedule, crash recovery via run_resilient, spill-corruption
checksum catches, fused->gather kernel fallback with identical tokens),
deadline shed/cancel accounting, and a seeded chaos matrix asserting
bit-identical replays with zero invariant violations and zero leaked
pages."""
import jax
import numpy as np
import pytest

from repro.checkpoint.store import load_snapshot, save_snapshot
from repro.configs import TINY
from repro.models.transformer import init_lm
from repro.serve import traffic
from repro.serve.engine import ContinuousEngine
from repro.serve.faults import Fault, FaultPlan, run_resilient

CFG = TINY.replace(n_repeats=2, d_model=64, head_dim=16, d_ff=128)

ZOO = [
    ("dense", CFG),
    ("gqa", CFG.replace(n_kv_heads=2)),
    ("swa", CFG.replace(attn_window=12)),
    ("int8-kv", CFG.replace(kv_cache_bits=8)),
    ("gqa-swa-int8", CFG.replace(n_kv_heads=2, attn_window=12,
                                 kv_cache_bits=8)),
]


@pytest.fixture(scope="module", autouse=True)
def _release_executables():
    # this module compiles many one-off engine variants (the zoo x cut
    # points, odd slot/page geometries for fingerprint tests); drop the
    # executables when the module finishes so the rest of the suite does
    # not carry their jit footprint in the same process
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def tiny_lm():
    return init_lm(CFG, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_bucket", 8)
    kw.setdefault("decode_block", 2)
    return ContinuousEngine(cfg, params, **kw)


def _submit_mixed(eng, vocab, *, seed=7):
    """Four requests that exercise preemption under n_slots=2: a long
    batch victim, two interactive arrivals that evict it, a late batch."""
    rng = np.random.default_rng(seed)
    spec = [(16, 0.0, 1), (4, 3.0, 0), (5, 5.0, 0), (6, 7.0, 1)]
    return [eng.submit(rng.integers(0, vocab, 8), max_new=mn,
                       arrival=t, priority=p) for mn, t, p in spec]


def _tokens(done):
    return {r.rid: tuple(r.tokens) for r in done}


def _step_n(eng, n):
    """Advance the virtual clock by hand, exactly like run(clock=None)."""
    steps = 0
    while not eng.sched.all_done() and steps < n:
        eng.step(float(eng.t))
        eng.t += 1
        steps += 1


def _assert_drained(eng):
    eng.pool.check_invariants()
    assert np.all(eng.pool.tables == -1), "pages mapped after drain"
    held = sum(len(h[1]) for h in eng._fault_holds)
    assert eng.pool.n_free == eng.spec.n_pages - 1 - held, \
        "leaked pages after drain"


# ------------------------------------------------ snapshot / restore

def test_snapshot_restore_token_identity_zoo(tiny_lm, tmp_path):
    """Kill the engine mid-trace at several cut points, restore the
    snapshot into a freshly built engine, and finish: greedy tokens are
    bit-identical to the uninterrupted run, across dense/GQA/SWA/int8-KV
    with preemption in flight. The dense entry also round-trips one
    snapshot through the on-disk store."""
    for name, cfg in ZOO:
        params = tiny_lm if cfg is CFG else \
            init_lm(cfg, jax.random.PRNGKey(0))
        base = _engine(cfg, params, preempt=True, age_promote=64.0)
        _submit_mixed(base, cfg.vocab_size)
        want = _tokens(base.run(max_steps=2000))
        _assert_drained(base)
        for cut in (2, 6):
            eng = _engine(cfg, params, preempt=True, age_promote=64.0)
            _submit_mixed(eng, cfg.vocab_size)
            _step_n(eng, cut)
            snap = eng.snapshot()
            if name == "dense" and cut == 6:
                path = str(tmp_path / "snap")
                save_snapshot(path, snap)
                snap = load_snapshot(path)
            fresh = _engine(cfg, params, preempt=True, age_promote=64.0)
            fresh.restore(snap)
            got = _tokens(fresh.run(max_steps=2000))
            assert got == want, \
                f"{name}: restore at step {cut} changed tokens"
            assert fresh.sched.stats() == base.sched.stats(), \
                f"{name}: restore at step {cut} changed accounting"
            _assert_drained(fresh)


def test_snapshot_fingerprint_mismatch_raises(tiny_lm):
    eng = _engine(CFG, tiny_lm)
    snap = eng.snapshot()
    for kw in ({"n_slots": 3}, {"decode_block": 4}, {"page_size": 16}):
        other = _engine(CFG, tiny_lm, **kw)
        with pytest.raises(ValueError, match="fingerprint"):
            other.restore(snap)
    gqa = _engine(CFG.replace(n_kv_heads=2),
                  init_lm(CFG.replace(n_kv_heads=2), jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="fingerprint"):
        gqa.restore(snap)


def test_snapshot_preserves_free_list_order(tiny_lm):
    """Allocation determinism: the restored pool hands out the same pages
    in the same order the original would have."""
    eng = _engine(CFG, tiny_lm)
    _submit_mixed(eng, CFG.vocab_size)
    _step_n(eng, 3)
    snap = eng.snapshot()
    fresh = _engine(CFG, tiny_lm)
    fresh.restore(snap)
    assert list(fresh.pool._free) == list(eng.pool._free)
    assert np.array_equal(fresh.pool.tables, eng.pool.tables)
    fresh.run(max_steps=2000)


# ------------------------------------------------------- fault kinds

def test_nan_quarantine_shields_cobatched_slot(tiny_lm):
    """A NaN-poisoned slot is quarantined by the isfinite sentinel (error
    recorded, pages freed); the co-batched slot's tokens are identical to
    a run where it decodes alone."""
    rng = np.random.default_rng(3)
    p0 = rng.integers(0, CFG.vocab_size, 8)
    p1 = rng.integers(0, CFG.vocab_size, 8)
    solo_eng = _engine(CFG, tiny_lm)
    solo = solo_eng.submit(p0, max_new=10)
    solo_eng.run(max_steps=500)
    eng = _engine(CFG, tiny_lm,
                  faults=FaultPlan([Fault(step=2, kind="nan_logits",
                                          slot=1)]))
    survivor = eng.submit(p0, max_new=10, arrival=0.0)
    victim = eng.submit(p1, max_new=10, arrival=0.0)
    eng.run(max_steps=500)
    assert victim.error == "nonfinite_logits"
    assert len(victim.tokens) < 10 + 1
    assert survivor.error is None
    assert survivor.tokens == solo.tokens, \
        "quarantine perturbed the co-batched slot"
    st = eng.fault_stats()
    assert st["n_nonfinite"] == 1 and st["n_quarantined"] == 1
    assert eng.sched.stats()["n_quarantined"] == 1
    _assert_drained(eng)


def test_pool_exhaust_delays_admission_then_releases(tiny_lm):
    """Held pages make admission wait; the hold releases on schedule, the
    delayed request still finishes with identical tokens, and the pool
    conserves pages throughout."""
    rng = np.random.default_rng(4)
    p0 = rng.integers(0, CFG.vocab_size, 8)
    p1 = rng.integers(0, CFG.vocab_size, 8)

    def drive(faults):
        eng = _engine(CFG, tiny_lm, n_pages=8, faults=faults)
        eng.debug = True
        # r0 outlives the hold window so its own retirement can't hand
        # pages to r1 while the hold is active
        r0 = eng.submit(p0, max_new=12, arrival=0.0)
        r1 = eng.submit(p1, max_new=4, arrival=2.0)
        eng.run(max_steps=500)
        _assert_drained(eng)
        admit_t = {e[2]: e[1] for e in eng.sched.events
                   if e[0] == "admit"}
        return eng, (r0.tokens, r1.tokens), admit_t

    _, base_toks, base_admit = drive(None)
    plan = FaultPlan([Fault(step=1, kind="pool_exhaust", pages=6,
                            duration=4)])
    eng, toks, admit = drive(plan)
    assert toks == base_toks
    assert admit[1] > base_admit[1], "hold did not delay admission"
    assert eng.fault_stats()["held_pages"] == 0, "hold never released"
    assert eng.fault_stats()["n_faults_applied"] == 1


def test_step_exception_crash_restore_resilient(tiny_lm, tmp_path):
    """run_resilient survives injected crashes: the engine is rebuilt from
    the last snapshot and the drained token streams are bit-identical to
    the fault-free run — both with in-memory snapshots and through the
    on-disk store."""
    trace = traffic.make_trace(kind="uniform", n=6, rate=1.0, seed=5,
                               vocab_size=CFG.vocab_size, prompt_len=(6, 10),
                               max_new=(4, 8))
    base_eng = _engine(CFG, tiny_lm, preempt=True, age_promote=64.0)
    base = traffic.replay(base_eng, trace, max_steps=2000)
    want = _tokens(base["requests"])

    def build():
        return _engine(CFG, tiny_lm, preempt=True, age_promote=64.0)

    plan = FaultPlan([Fault(step=4, kind="step_exception"),
                      Fault(step=9, kind="step_exception")])
    for store_dir in (None, str(tmp_path / "snap")):
        res = run_resilient(build, trace, faults=plan, snapshot_every=3,
                            store_dir=store_dir, max_steps=2000)
        assert res["n_crashes"] == 2
        assert res["n_snapshots"] > 0
        assert _tokens(res["requests"]) == want, \
            "crash recovery changed the token streams"
        _assert_drained(res["engine"])


def test_spill_corruption_caught_on_restore(tiny_lm):
    """A spill snapshot corrupted in host RAM fails its checksum at
    restore time: the victim is quarantined (never resumed on garbage KV),
    the preemptor's tokens are untouched, and no page leaks."""
    rng = np.random.default_rng(11)
    batch_p = rng.integers(0, CFG.vocab_size, 8)
    inter_p = rng.integers(0, CFG.vocab_size, 8)
    solo_eng = _engine(CFG, tiny_lm, n_slots=1, max_len=40,
                       decode_block=1, preempt=True)
    solo = solo_eng.submit(inter_p, max_new=4)
    solo_eng.run(max_steps=500)
    plan = FaultPlan([Fault(step=1, kind="spill_corrupt")])
    eng = _engine(CFG, tiny_lm, n_slots=1, max_len=40, decode_block=1,
                  preempt=True, faults=plan)
    victim = eng.submit(batch_p, max_new=20, arrival=0.0, priority=1)
    inter = eng.submit(inter_p, max_new=4, arrival=4.0, priority=0)
    eng.run(max_steps=500)
    assert victim.n_preempts == 1
    assert victim.error == "spill_corrupt"
    assert inter.tokens == solo.tokens
    st = eng.fault_stats()
    assert st["n_spill_corruptions"] == 1
    assert st["n_spill_checksum_fails"] == 1
    assert st["n_quarantined"] == 1
    _assert_drained(eng)


def test_kernel_fault_falls_back_with_identical_tokens(tiny_lm):
    """A failed fused paged-attention dispatch downgrades the engine to
    the gather path mid-trace; the token streams don't change (the two
    impls are bitwise-identical) and the fallback is counted."""
    base = _engine(CFG, tiny_lm, paged_attn="fused")
    _submit_mixed(base, CFG.vocab_size)
    want = _tokens(base.run(max_steps=2000))
    plan = FaultPlan([Fault(step=3, kind="kernel_fault")])
    eng = _engine(CFG, tiny_lm, paged_attn="fused", faults=plan)
    _submit_mixed(eng, CFG.vocab_size)
    got = _tokens(eng.run(max_steps=2000))
    assert got == want, "fused->gather fallback changed tokens"
    st = eng.fault_stats()
    assert st["n_kernel_fallbacks"] == 1
    assert st["paged_attn_impl"] == "gather"
    _assert_drained(eng)


def test_real_decode_dispatch_error_propagates(tiny_lm, monkeypatch):
    """Only the injected kernel_fault degrades fused -> gather: any other
    dispatch failure (a kernel that does not lower) must surface, not be
    served through the oracle path."""
    from repro.serve import engine as engine_mod

    def broken(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    eng = _engine(CFG, tiny_lm, paged_attn="fused")
    _submit_mixed(eng, CFG.vocab_size)
    monkeypatch.setattr(engine_mod, "_paged_decode_scan_jit", broken)
    with pytest.raises(RuntimeError, match="Mosaic"):
        eng.run(max_steps=2000)
    assert eng.n_kernel_fallbacks == 0
    assert eng.cfg.paged_attn_impl == "fused"


# ------------------------------------------------- deadlines / shedding

def test_deadline_shed_cancel_completed_disjoint(tiny_lm):
    """Deadline enforcement splits the trace into three disjoint
    populations — shed from the queue, cancelled mid-run, completed — and
    the scheduler counters agree with the per-request flags. The event
    log is identical across two replays."""
    trace = traffic.make_trace(kind="bursty", n=8, rate=1.0, seed=3,
                               vocab_size=CFG.vocab_size, prompt_len=(6, 10),
                               max_new=(4, 16), burst_len=0.4, idle_len=6.0,
                               burst_rate_mult=8.0, deadline=8.0)
    runs = []
    for _ in range(2):
        eng = _engine(CFG, tiny_lm, n_slots=1, max_len=48, decode_block=1)
        report = traffic.replay(eng, trace, max_steps=2000)
        reqs = report["requests"]
        runs.append((list(eng.sched.events),
                     eng.sched.stats(), _tokens(reqs)))
        shed = {r.rid for r in reqs if r.shed}
        cancelled = {r.rid for r in reqs if r.cancelled}
        completed = {r.rid for r in reqs
                     if not (r.shed or r.cancelled or r.rejected
                             or r.error)}
        assert shed and cancelled and completed, \
            "trace must exercise all three populations"
        assert not (shed & cancelled) and not (shed & completed) \
            and not (cancelled & completed)
        assert shed | cancelled | completed == {r.rid for r in reqs}
        stats = eng.sched.stats()
        assert stats["n_shed"] == len(shed)
        assert stats["n_cancelled"] == len(cancelled)
        assert stats["n_finished_ok"] + stats["n_finished_preempted"] \
            == len(completed)
        # shed requests never produced a token; cancelled ones never
        # reached their full budget
        assert all(not r.tokens for r in reqs if r.shed)
        assert all(len(r.tokens) < r.max_new + 1
                   for r in reqs if r.cancelled)
        assert report["overall"]["n_shed"] == len(shed)
        _assert_drained(eng)
    assert runs[0] == runs[1], "deadline replay is not deterministic"


# -------------------------------------------------- debug mode / leaks

def test_repro_debug_env_validates_every_step(tiny_lm, monkeypatch):
    """REPRO_DEBUG=1 arms the per-step invariant check at construction;
    a clean preempting trace passes it on every step."""
    monkeypatch.setenv("REPRO_DEBUG", "1")
    eng = _engine(CFG, tiny_lm, preempt=True, age_promote=64.0)
    assert eng.debug
    _submit_mixed(eng, CFG.vocab_size)
    eng.run(max_steps=2000)
    _assert_drained(eng)


def test_repro_debug_catches_corrupted_mirror(tiny_lm):
    """The debug check actually fires: corrupting a slot mirror by hand
    fails the very next step instead of surfacing at drain."""
    eng = _engine(CFG, tiny_lm)
    eng.debug = True
    eng.submit(np.arange(8, dtype=np.int32), max_new=8)
    _step_n(eng, 2)
    eng.cur_len[0] += 1                              # simulated corruption
    with pytest.raises(AssertionError, match="disagrees|exceeds"):
        eng.step(float(eng.t))


def test_mixed_trace_leak_audit(tiny_lm):
    """Every early-exit path at once — preemption, deadline shed/cancel,
    nan quarantine, corrupt-spill quarantine — and the pool still drains
    to empty with invariants intact after every step."""
    plan = FaultPlan([Fault(step=1, kind="spill_corrupt"),
                      Fault(step=6, kind="nan_logits", slot=0),
                      Fault(step=9, kind="pool_exhaust", pages=4,
                            duration=3),
                      Fault(step=12, kind="latency_spike", duration=4)])
    trace = traffic.make_trace(kind="bursty", n=10, rate=1.0, seed=9,
                               vocab_size=CFG.vocab_size, prompt_len=(6, 12),
                               max_new=(4, 14), burst_len=0.5, idle_len=5.0,
                               burst_rate_mult=8.0, deadline=20.0)
    eng = _engine(CFG, tiny_lm, preempt=True, age_promote=32.0,
                  faults=plan)
    eng.debug = True                 # invariants checked after every step
    report = traffic.replay(eng, trace, max_steps=2000)
    st = eng.fault_stats()
    assert st["n_faults_applied"] == len(plan)
    _assert_drained(eng)
    assert eng._fault_holds == []
    assert all(r.finished_at is not None for r in report["requests"])


# --------------------------------------------------- seeded chaos matrix

def test_chaos_matrix_deterministic_and_leak_free(tiny_lm):
    """The acceptance gate: seeded fault schedules (including crashes)
    replay bit-for-bit — same events, same fault accounting, same token
    streams — with per-step invariant checks on and zero leaked pages;
    untouched survivors match the fault-free baseline."""
    trace = traffic.make_trace(kind="bursty", n=8, rate=1.0, seed=13,
                               vocab_size=CFG.vocab_size, prompt_len=(6, 10),
                               max_new=(4, 10), burst_len=0.5, idle_len=6.0,
                               burst_rate_mult=8.0)
    base_eng = _engine(CFG, tiny_lm, preempt=True, age_promote=64.0)
    base = traffic.replay(base_eng, trace, max_steps=2000)
    want = _tokens(base["requests"])

    def build():
        eng = _engine(CFG, tiny_lm, preempt=True, age_promote=64.0)
        eng.debug = True
        return eng

    total_crashes = 0
    for seed in range(3):
        plan = FaultPlan.seeded(seed, n_steps=15, n_slots=2, n_faults=4,
                                crashes=1)
        runs = []
        for _ in range(2):
            res = run_resilient(build, trace, faults=plan,
                                snapshot_every=4, max_steps=4000)
            eng = res["engine"]
            _assert_drained(eng)
            untouched = {r.rid: tuple(r.tokens) for r in res["requests"]
                         if not (r.error or r.shed or r.cancelled
                                 or r.n_preempts)}
            for rid, toks in untouched.items():
                assert toks == want[rid], \
                    f"seed {seed}: fault plan perturbed survivor {rid}"
            runs.append((res["n_crashes"], list(eng.sched.events),
                         eng.fault_stats(), _tokens(res["requests"])))
        assert runs[0] == runs[1], f"seed {seed}: chaos replay diverged"
        total_crashes += runs[0][0]
    # the matrix as a whole exercised crash recovery (a crash scheduled
    # past the trace's natural end is legitimately unreached)
    assert total_crashes >= 1


# ------------------------------------------------------- FaultPlan units

def test_fault_plan_seeded_reproducible_and_ordered():
    a = FaultPlan.seeded(42, n_steps=32, n_slots=4, n_faults=8, crashes=2)
    b = FaultPlan.seeded(42, n_steps=32, n_slots=4, n_faults=8, crashes=2)
    assert a.faults == b.faults
    assert len(a) == 10
    assert sum(f.kind == "step_exception" for f in a) == 2
    steps = [f.step for f in a]
    assert steps == sorted(steps)
    for s in set(steps):
        assert a.at(s) == [f for f in a if f.step == s]


def test_fault_plan_drop_removes_one_occurrence():
    f = Fault(step=3, kind="step_exception")
    plan = FaultPlan([f, f, Fault(step=1, kind="nan_logits")])
    assert len(plan.drop(f)) == 2
    assert len(plan.drop(f).drop(f)) == 1
    with pytest.raises(ValueError):
        FaultPlan([Fault(step=0, kind="not_a_fault")])
