"""Prefix cache + chunked prefill: token identity against the monolithic
no-sharing engine, page refcount/index invariants, and the scheduler
regressions that rode along (width gating, insort intake, first-token
reproducibility across prefill batching)."""
import jax
import numpy as np
import pytest

from repro.configs import TINY
from repro.models.transformer import init_lm
from repro.serve.engine import ContinuousEngine
from repro.serve.kvcache import PagePool, PageSpec
from repro.serve.scheduler import Request, Scheduler

CFG = TINY.replace(n_repeats=2, d_model=64, head_dim=16, d_ff=128)


@pytest.fixture(scope="module")
def tiny_lm():
    return init_lm(CFG, jax.random.PRNGKey(0))


def _shared_prefix_requests(rng, n_shared=32, tails=((8, 5), (13, 4),
                                                     (24, 6), (5, 5))):
    """Requests whose prompts all start with the same n_shared tokens."""
    system = rng.integers(0, CFG.vocab_size, n_shared)
    return [(np.concatenate([system, rng.integers(0, CFG.vocab_size, t)]), m)
            for t, m in tails]


def _run(cfg, params, reqs, *, arrivals=None, **kw):
    eng = ContinuousEngine(cfg, params, n_slots=3, max_len=128, page_size=16,
                           prefill_bucket=8, **kw)
    for i, (prompt, max_new) in enumerate(reqs):
        arrival = float(i) if arrivals is None else arrivals[i]
        eng.submit(prompt, max_new=max_new, arrival=arrival)
    done = eng.run(max_steps=2000)
    return eng, {r.rid: r.tokens for r in done}


# ---------------------------------------------------------- scheduler fixes

def test_can_alloc_gates_on_block_table_width():
    """A request wider than one block-table row is un-admittable even when
    the pool has plenty of free pages (the old check only counted pages,
    so admit() crashed inside alloc instead of queueing cleanly)."""
    spec = PageSpec(n_pages=17, page_size=4, max_pages=2)
    pool = PagePool(spec, n_slots=2)
    assert pool.n_free == 16
    assert pool.can_alloc(8)            # 2 pages == table width
    assert not pool.can_alloc(9)        # 3 pages > width, 16 free
    with pytest.raises(ValueError):
        pool.alloc(0, 9)


def test_scheduler_rejects_overwide_request_without_raising():
    """Driving the Scheduler directly (no engine.submit pre-check): an
    over-wide budget retires as rejected and the queue keeps moving."""
    spec = PageSpec(n_pages=17, page_size=4, max_pages=2)
    pool = PagePool(spec, n_slots=2)
    sched = Scheduler(2, pool)
    wide = Request(rid=0, prompt=np.zeros(6, np.int32), max_new=6)  # 3 pages
    ok = Request(rid=1, prompt=np.zeros(4, np.int32), max_new=4)    # 2 pages
    sched.submit(wide)
    sched.submit(ok)
    admitted = sched.admit(0.0)         # must not raise
    assert [r.rid for _, r in admitted] == [1]
    assert wide.rejected and wide.done and not wide.tokens
    assert not ok.rejected
    pool.check_invariants()
    # the rejected request is reported with the finished ones
    assert wide in sched.finished


def test_submit_insort_intake_order_large_n():
    """Shuffled large-N submission ingests in arrival order, ties stable."""
    spec = PageSpec(n_pages=5, page_size=4, max_pages=4)
    sched = Scheduler(1, PagePool(spec, n_slots=1))
    n = 2000
    rng = np.random.default_rng(0)
    arrivals = rng.integers(0, 50, n).astype(float)   # many ties
    for rid, arr in enumerate(arrivals):
        sched.submit(Request(rid=rid, prompt=np.zeros(1, np.int32),
                             max_new=1, arrival=float(arr)))
    sched._ingest(now=25.0)
    got = [(r.arrival, r.rid) for r in sched.queue]
    assert all(a <= 25.0 for a, _ in got)
    assert got == sorted(got)           # arrival order, rid-stable ties
    assert len(got) + len(sched._pending) == n
    sched._ingest(now=1e9)
    assert not sched._pending and len(sched.queue) == n


def test_first_token_reproducible_across_prefill_batch(tiny_lm):
    """Sampled (temperature > 0) runs give the same tokens whether admitted
    prompts prefill one-per-call or co-batched: the first token comes from
    a per-request device key, not a host RNG consumed in batch order."""
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, CFG.vocab_size, plen), 6)
            for plen in (8, 12, 16, 9, 24, 5)]
    outs = []
    for batch in (1, 8):
        eng = ContinuousEngine(CFG, tiny_lm, n_slots=6, max_len=64,
                               page_size=16, prefill_bucket=8,
                               prefill_batch=batch, temperature=0.8, seed=11)
        for prompt, max_new in reqs:
            eng.submit(prompt, max_new=max_new, arrival=0.0)
        outs.append({r.rid: r.tokens for r in eng.run(max_steps=2000)})
    assert outs[0] == outs[1]


# ------------------------------------------------- token identity + savings

def test_shared_prefix_reduces_prefill_and_keeps_tokens(tiny_lm):
    """Acceptance: 16 requests sharing a 2-page prefix prefill measurably
    fewer tokens than the no-share baseline and emit identical greedy
    tokens; admission never raises; the pool drains consistent."""
    rng = np.random.default_rng(0)
    tails = [(4 + 3 * (i % 7), 3 + i % 4) for i in range(16)]
    reqs = _shared_prefix_requests(rng, n_shared=32, tails=tails)
    base_eng, base = _run(CFG, tiny_lm, reqs)
    share_eng, share = _run(CFG, tiny_lm, reqs, prefix_share=True)
    assert share == base
    assert share_eng.n_prefill_tokens < base_eng.n_prefill_tokens
    assert share_eng.n_shared_tokens > 0
    # all shared tokens were whole pages of the common 32-token prefix
    assert share_eng.n_shared_tokens % share_eng.spec.page_size == 0
    share_eng.pool.check_invariants()
    assert np.all(share_eng.pool.tables == -1)      # every slot unmapped
    # conservation incl. the cache: free + cached == allocatable
    assert (share_eng.pool.n_free + share_eng.pool.n_cached
            == share_eng.spec.n_pages - 1)


def test_token_identity_zoo_prefix_and_chunked(tiny_lm):
    """Prefix-hit and chunked-prefill runs emit the same greedy tokens as
    the monolithic no-sharing baseline across the attention zoo the
    features cover: dense, GQA, SWA, int8-KV."""
    variants = [
        ("dense", CFG),
        ("gqa", CFG.replace(n_kv_heads=2)),
        ("swa", CFG.replace(attn_window=12)),
        ("int8-kv", CFG.replace(kv_cache_bits=8)),
        ("gqa-swa-int8", CFG.replace(n_kv_heads=2, attn_window=12,
                                     kv_cache_bits=8)),
    ]
    rng = np.random.default_rng(7)
    reqs = _shared_prefix_requests(rng)
    for name, cfg in variants:
        params = tiny_lm if cfg is CFG else init_lm(cfg, jax.random.PRNGKey(0))
        _, base = _run(cfg, params, reqs)
        for kw in (dict(prefix_share=True), dict(chunked_prefill=16),
                   dict(prefix_share=True, chunked_prefill=16)):
            eng, out = _run(cfg, params, reqs, **kw)
            assert out == base, f"{name} diverged under {kw}"
            eng.pool.check_invariants()


def test_fused_paged_attention_reads_stitched_tables(tiny_lm):
    """The fused decode kernel walks block tables whose rows stitch shared
    prefix pages before owned tail pages — same tokens as the gather
    oracle on the same prefix-shared, chunked workload."""
    cfg = CFG.replace(n_kv_heads=2, kv_cache_bits=8)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    reqs = _shared_prefix_requests(np.random.default_rng(5))
    outs = {}
    for impl in ("fused", "gather"):
        eng, outs[impl] = _run(cfg, params, reqs, paged_attn=impl,
                               prefix_share=True, chunked_prefill=16)
        eng.pool.check_invariants()
    assert outs["fused"] == outs["gather"]


def test_chunked_prefill_interleaves_decode(tiny_lm):
    """A long prompt split into chunks must not stall decode: a short
    request admitted alongside it keeps emitting tokens between chunks
    and finishes while the long prompt is still prefilling."""
    rng = np.random.default_rng(2)
    long_p = rng.integers(0, CFG.vocab_size, 96)     # 6 chunks of 16
    short_p = rng.integers(0, CFG.vocab_size, 8)
    eng = ContinuousEngine(CFG, tiny_lm, n_slots=2, max_len=128,
                           page_size=16, prefill_bucket=8, decode_block=1,
                           chunked_prefill=16)
    long_r = eng.submit(long_p, max_new=4, arrival=0.0)
    short_r = eng.submit(short_p, max_new=4, arrival=0.0)
    eng.run(max_steps=500)
    # short finished decoding strictly before the long prompt produced its
    # first token (virtual clock: one step() per tick)
    assert short_r.finished_at < long_r.first_token_at
    assert eng.n_prefills >= 6 + 1
    # and the chunked long prompt decoded the same tokens as monolithic
    _, base = _run(CFG, tiny_lm, [(long_p, 4)])
    assert long_r.tokens == base[0]


def test_chunked_rejects_unsupported_archs(tiny_lm):
    """SSM state is not page-addressed, so an SSM hybrid refuses prefix
    sharing and chunked prefill. Latent attention (MLA) serves both: its
    later chunks and shared-prefix suffixes attend to the latent context
    read back through the block table, and decode the same greedy tokens
    as monolithic, unshared prefill."""
    from repro.configs import get_smoke_config

    cfg = get_smoke_config("jamba-1.5-large-398b")
    params = init_lm(cfg, jax.random.PRNGKey(1))
    for kw in (dict(prefix_share=True), dict(chunked_prefill=16)):
        with pytest.raises(NotImplementedError):
            ContinuousEngine(cfg, params, n_slots=2, max_len=64,
                             page_size=8, **kw)
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    params = init_lm(cfg, jax.random.PRNGKey(1))
    reqs = _shared_prefix_requests(np.random.default_rng(4))
    _, base = _run(cfg, params, reqs)
    for kw in (dict(prefix_share=True), dict(chunked_prefill=16)):
        eng, got = _run(cfg, params, reqs, **kw)
        assert got == base, kw
    assert eng.n_prefills > len(reqs)              # prompts went in chunks


# --------------------------------------------------------- pool invariants

def test_pool_refcount_lifecycle_direct():
    """Shared pages are referenced not copied, survive holder retirement
    via the index reference, and are never freed while any slot holds
    them; release conserves pages."""
    spec = PageSpec(n_pages=13, page_size=4, max_pages=6)
    pool = PagePool(spec, n_slots=3, prefix_cache=True)
    prompt = np.arange(9, dtype=np.int32)            # 2 full pages + 1 tok
    pool.alloc(0, 12)                                # 3 pages
    pool.register_prefix(prompt, 0)                  # pages 0,1 of slot 0
    shared = pool.lookup_prefix(prompt)
    assert len(shared) == 2
    assert pool.refcount[shared].tolist() == [2, 2]  # slot + index
    pool.check_invariants()

    # a second slot stitches the shared pages; refcount rises
    assert pool.can_alloc(12, shared_pages=shared)
    pool.alloc(1, 12, shared_pages=shared)
    assert pool.tables[1][:2].tolist() == shared
    assert pool.refcount[shared].tolist() == [3, 3]
    pool.check_invariants()

    # original holder retires: shared pages stay (slot 1 + index hold them)
    pool.release(0)
    assert pool.refcount[shared].tolist() == [2, 2]
    assert not set(shared) & set(pool._free)
    pool.release(1)
    assert pool.refcount[shared].tolist() == [1, 1]  # index only: cached
    assert not set(shared) & set(pool._free)
    pool.check_invariants()
    assert pool.n_free + pool.n_cached == spec.n_pages - 1

    # a same-prefix lookup still hits after every holder retired
    assert pool.lookup_prefix(prompt) == shared


def test_prefix_cache_lookup_is_strict_prefix():
    """A lookup never covers the whole prompt (the suffix prefill must
    keep >= 1 token) and never matches when any earlier token differs."""
    spec = PageSpec(n_pages=13, page_size=4, max_pages=6)
    pool = PagePool(spec, n_slots=2, prefix_cache=True)
    prompt = np.arange(8, dtype=np.int32)            # exactly 2 pages
    pool.alloc(0, 8)
    pool.register_prefix(prompt, 0)
    # identical prompt: only the first page may be reused (strict prefix)
    assert len(pool.lookup_prefix(prompt)) == 1
    # longer prompt with the same head: both pages hit
    assert len(pool.lookup_prefix(np.arange(12, dtype=np.int32))) == 2
    # same second page content but different first page: no hit at all
    other = np.concatenate([np.full(4, 99, np.int32),
                            np.arange(4, 8, dtype=np.int32), [1]])
    assert pool.lookup_prefix(other) == []


def test_eviction_prefers_chain_leaves():
    """Evicting a cached chain drops its deepest entry first: taking the
    head would strand the descendants — unreachable via lookup (which
    walks from page 0) yet still holding pages."""
    spec = PageSpec(n_pages=4, page_size=4, max_pages=3)
    pool = PagePool(spec, n_slots=1, prefix_cache=True)
    prompt = np.arange(12, dtype=np.int32)           # 3 full pages
    pool.alloc(0, 12)
    pool.register_prefix(prompt, 0)
    chain = pool.lookup_prefix(np.arange(16, dtype=np.int32))
    assert len(chain) == 3
    pool.release(0)                                  # index-only: evictable
    pool._evict_one()
    pool.check_invariants()
    # the 2-page head of the chain is still reachable, the leaf is gone
    assert pool.lookup_prefix(np.arange(16, dtype=np.int32)) == chain[:2]
    pool._evict_one()
    assert pool.lookup_prefix(np.arange(16, dtype=np.int32)) == chain[:1]
    pool.check_invariants()


def test_prefix_cache_eviction_under_pressure(tiny_lm):
    """A pool too small to cache every distinct prefix evicts index-only
    pages to admit new work; everything completes and conserves pages."""
    rng = np.random.default_rng(4)
    # 11 allocatable pages; each request needs 3 pages (prompt 32 + 8 new)
    eng = ContinuousEngine(CFG, tiny_lm, n_slots=2, max_len=48, page_size=16,
                           n_pages=12, prefill_bucket=8, prefix_share=True)
    for i in range(6):                               # 6 distinct prefixes
        prompt = rng.integers(0, CFG.vocab_size, 32 + (i % 2))
        eng.submit(prompt, max_new=8, arrival=float(i))
    done = eng.run(max_steps=2000)
    assert len(done) == 6 and all(r.done and not r.rejected for r in done)
    eng.pool.check_invariants()
    assert np.all(eng.pool.tables == -1)
    assert eng.pool.n_free + eng.pool.n_cached == eng.spec.n_pages - 1


def test_pool_fuzz_invariants_hold_every_step(tiny_lm):
    """Randomized traffic with colliding prefixes (tiny alphabet): the
    refcount/index/free-list invariants hold after every scheduler step
    and admission never raises."""
    rng = np.random.default_rng(9)
    eng = ContinuousEngine(CFG, tiny_lm, n_slots=3, max_len=64, page_size=8,
                           n_pages=20, prefill_bucket=8, prefix_share=True,
                           chunked_prefill=8)
    for i in range(12):
        plen = int(rng.integers(4, 40))
        prompt = rng.integers(0, 3, plen)            # heavy prefix collisions
        eng.submit(prompt, max_new=int(rng.integers(1, 6)),
                   arrival=float(rng.integers(0, 6)))
    steps = 0
    while not eng.sched.all_done():
        eng.step(float(steps))
        eng.pool.check_invariants()
        assert eng.pool.refcount.min() >= 0
        steps += 1
        assert steps < 1000
    assert len(eng.sched.finished) == 12
