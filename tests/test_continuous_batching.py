"""Continuous-batching engine tests: token equivalence against the static
engine, paged-cache correctness across architectures, and page-pool
invariants (no leaks, admission blocks on exhaustion)."""
import jax
import numpy as np
import pytest

from repro.configs import TINY
from repro.models.transformer import init_lm
from repro.serve.engine import ContinuousEngine, ServeEngine
from repro.serve.kvcache import PagePool, PageSpec, default_page_spec

CFG = TINY.replace(n_repeats=2, d_model=64, head_dim=16, d_ff=128)

# 16 requests / 8 slots, mixed prompt lengths 8-64, staggered arrivals.
# Four distinct (prompt_len, max_new) shapes keep jit compile count small.
WORKLOAD = [(8, 6), (16, 4), (32, 8), (64, 5)] * 4


@pytest.fixture(scope="module")
def tiny_lm():
    return init_lm(CFG, jax.random.PRNGKey(0))


def _make_requests(rng):
    return [(rng.integers(0, CFG.vocab_size, plen), max_new, float(i % 5))
            for i, (plen, max_new) in enumerate(WORKLOAD)]


def test_token_equivalence_mixed_lengths_staggered_arrivals(tiny_lm):
    """Greedy continuous-batching output == static per-request output."""
    reqs = _make_requests(np.random.default_rng(0))
    eng = ContinuousEngine(CFG, tiny_lm, n_slots=8, max_len=128,
                           page_size=16, prefill_bucket=8)
    handles = [eng.submit(p, max_new=m, arrival=a) for p, m, a in reqs]
    done = eng.run(max_steps=2000)
    assert len(done) == len(reqs) and all(r.done for r in done)

    static = ServeEngine(CFG, tiny_lm)
    for (prompt, max_new, _), handle in zip(reqs, handles):
        ref = static.generate(prompt[None, :], max_new=max_new,
                              temperature=0.0)
        assert handle.tokens == list(ref.tokens[0]), \
            f"request {handle.rid} diverged"
    # every page returned once all requests retired
    assert eng.pool.n_free == eng.spec.n_pages - 1
    assert np.all(eng.pool.tables == -1)


def test_admission_blocks_when_pool_exhausted(tiny_lm):
    """More slots than pages: admission must wait for pages, not overflow."""
    # pool covers exactly two concurrent requests (budget 16 tokens = 2
    # pages of 8), plus the reserved scratch page
    spec_pages = 1 + 2 * 2
    # decode_block=1 so slot occupancy is observable at step boundaries
    eng = ContinuousEngine(CFG, tiny_lm, n_slots=4, max_len=16, page_size=8,
                           n_pages=spec_pages, prefill_bucket=8,
                           decode_block=1)
    rng = np.random.default_rng(1)
    for i in range(5):
        eng.submit(rng.integers(0, CFG.vocab_size, 8), max_new=8)

    max_concurrent = 0
    steps = 0
    while not eng.sched.all_done():
        eng.step(float(steps))
        max_concurrent = max(max_concurrent, len(eng.sched.active_slots()))
        assert eng.pool.n_free >= 0
        steps += 1
        assert steps < 500
    assert max_concurrent == 2          # free slots existed, pages gated
    assert len(eng.sched.finished) == 5
    assert eng.pool.n_free == spec_pages - 1


def test_page_pool_alloc_release_invariants():
    spec = PageSpec(n_pages=9, page_size=4, max_pages=4)
    pool = PagePool(spec, n_slots=3)
    assert pool.n_free == 8
    pool.alloc(0, 9)                    # 3 pages
    pool.alloc(1, 16)                   # 4 pages
    assert pool.n_free == 1
    assert not pool.can_alloc(8)        # 2 pages > 1 free
    with pytest.raises(RuntimeError):
        pool.alloc(2, 8)
    pool.release(0)
    assert pool.n_free == 4
    pool.alloc(2, 8)
    pool.release(1)
    pool.release(2)
    assert pool.n_free == 8
    assert np.all(pool.tables == -1)
    # no page handed out twice: allocate everything, check uniqueness
    pool.alloc(0, 16)
    pool.alloc(1, 16)
    held = pool.tables[pool.tables >= 0]
    assert len(set(held.tolist())) == len(held) == 8


def test_eos_retires_slot_early(tiny_lm):
    """A request hitting EOS frees its slot and pages before max_new."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, CFG.vocab_size, 8)
    ref = ServeEngine(CFG, tiny_lm).generate(prompt[None, :], max_new=8,
                                             temperature=0.0)
    eos = int(ref.tokens[0, 2])         # third greedy token acts as EOS
    eng = ContinuousEngine(CFG, tiny_lm, n_slots=2, max_len=32, page_size=8,
                           prefill_bucket=8, eos_id=eos)
    handle = eng.submit(prompt, max_new=8)
    eng.run(max_steps=200)
    assert handle.tokens[-1] == eos
    assert len(handle.tokens) == 3      # stopped at EOS, not max_new
    assert eng.pool.n_free == eng.spec.n_pages - 1


def test_moe_pad_tokens_do_not_shift_routing():
    """Left-pad junk routes to no expert and never changes real tokens'
    results: dropless routing gives identical real-row outputs, and the
    pads add no rows to the routing counts."""
    import jax.numpy as jnp

    from repro.models.config import MoEConfig
    from repro.models.mlp_moe import apply_moe, init_moe

    cfg = CFG.replace(moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=64,
                                    capacity_factor=1.0))
    p = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, CFG.d_model)) * 0.3
    y_ref, aux_ref = apply_moe(cfg, p, x)
    pad = jax.random.normal(jax.random.PRNGKey(2), (1, 4, CFG.d_model)) * 5.0
    xp = jnp.concatenate([pad, x], axis=1)
    valid = jnp.concatenate([jnp.zeros((1, 4), bool),
                             jnp.ones((1, 12), bool)], axis=1)
    y_pad, aux_pad = apply_moe(cfg, p, xp, valid=valid)
    np.testing.assert_array_equal(np.asarray(y_pad[:, 4:]),
                                  np.asarray(y_ref))
    assert int(aux_pad["load"][0]) == int(aux_ref["load"][0]) == 12 * 2


def test_token_equivalence_mla_and_hybrid():
    """Paged serving matches the static engine across MLA, SSM-hybrid and
    SWA/MoE architectures (single-request prefill batches: capacity-MoE
    routing is cross-token, so co-batched prefills may legitimately differ
    when capacity binds — see DESIGN.md)."""
    from repro.configs import get_smoke_config

    for arch, bucket in [("deepseek-v2-lite-16b", 8),
                         ("jamba-1.5-large-398b", 1),
                         ("mixtral-8x22b", 8)]:
        cfg = get_smoke_config(arch)
        params = init_lm(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        static = ServeEngine(cfg, params)
        eng = ContinuousEngine(cfg, params, n_slots=3, max_len=64,
                               page_size=8, prefill_bucket=bucket,
                               prefill_batch=1)
        reqs = [(rng.integers(0, cfg.vocab_size, plen), max_new)
                for plen, max_new in [(8, 4), (12, 5), (16, 3), (9, 4)]]
        for i, (prompt, max_new) in enumerate(reqs):
            eng.submit(prompt, max_new=max_new, arrival=float(i % 2))
        done = eng.run(max_steps=500)
        for (prompt, max_new), r in zip(reqs, done):
            ref = static.generate(prompt[None], max_new=max_new,
                                  temperature=0.0)
            assert r.tokens == list(ref.tokens[0]), f"{arch} rid {r.rid}"
        assert eng.pool.n_free == eng.spec.n_pages - 1


def test_quantized_moe_token_equivalence():
    """Continuous-batching greedy tokens on a W4 MoE model (packed via the
    engines' quant_bits plumbing -> quantize_params_for_serving) match
    per-request static decoding with the same quantized params, so the
    expert-batched / decode-shaped kernel dispatch sits under the serving
    stack without changing tokens."""
    import jax.numpy as jnp

    from repro.core.quant.types import QuantizedTensor
    from repro.models.config import LayerSpec, MoEConfig
    from repro.utils.tree import tree_get

    cfg = CFG.replace(
        pattern=(LayerSpec(kind="attn", mlp="moe"),),
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=64,
                      capacity_factor=1.0))
    params = init_lm(cfg, jax.random.PRNGKey(0))

    static = ServeEngine(cfg, params, quant_bits=4, quant_group=32)
    eng = ContinuousEngine(cfg, params, n_slots=3, max_len=64, page_size=8,
                           prefill_bucket=8, prefill_batch=1,
                           quant_bits=4, quant_group=32)
    # both engines packed identically, experts included (stacked packed
    # layout: scan dim L x expert dim E in front of (K/vpb, N))
    wq = tree_get(eng.params, "stack/p0/moe/experts/wi")["w"]
    assert isinstance(wq, QuantizedTensor) and wq.bits == 4
    assert wq.qw.ndim == 4 and wq.qw.dtype == jnp.uint8

    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, cfg.vocab_size, plen), max_new)
            for plen, max_new in [(8, 4), (12, 5), (16, 3), (9, 4)]]
    for i, (prompt, max_new) in enumerate(reqs):
        eng.submit(prompt, max_new=max_new, arrival=float(i % 2))
    done = eng.run(max_steps=500)
    assert len(done) == len(reqs)
    for (prompt, max_new), r in zip(reqs, done):
        ref = static.generate(prompt[None], max_new=max_new,
                              temperature=0.0)
        assert r.tokens == list(ref.tokens[0]), f"quantized rid {r.rid}"
    assert eng.pool.n_free == eng.spec.n_pages - 1


def test_fused_paged_attention_token_equivalence():
    """Greedy tokens from the fused paged-attention decode kernel match the
    gather->dequant->einsum oracle path across the zoo axes the kernel
    covers: dense MHA, GQA (group > 1), sliding-window, and int8 KV."""
    variants = [
        ("dense", CFG),
        ("gqa", CFG.replace(n_kv_heads=2)),
        ("swa", CFG.replace(attn_window=12)),
        ("int8-kv", CFG.replace(kv_cache_bits=8)),
        ("gqa-swa-int8", CFG.replace(n_kv_heads=2, attn_window=12,
                                     kv_cache_bits=8)),
    ]
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, CFG.vocab_size, plen), max_new)
            for plen, max_new in [(8, 5), (13, 6), (24, 4)]]
    for name, cfg in variants:
        params = init_lm(cfg, jax.random.PRNGKey(0))
        assert cfg.paged_attn_impl == "fused"          # default path
        outs = {}
        for impl in ("fused", "gather"):
            eng = ContinuousEngine(cfg, params, n_slots=3, max_len=64,
                                   page_size=8, prefill_bucket=8,
                                   paged_attn=impl)
            for i, (prompt, max_new) in enumerate(reqs):
                eng.submit(prompt, max_new=max_new, arrival=float(i % 2))
            done = eng.run(max_steps=500)
            outs[impl] = [r.tokens for r in done]
            assert eng.pool.n_free == eng.spec.n_pages - 1
        assert outs["fused"] == outs["gather"], f"{name} diverged"


def test_default_page_spec_capacity():
    spec = default_page_spec(n_slots=4, max_len=100, page_size=16)
    assert spec.max_pages == 7
    assert spec.n_pages == 1 + 4 * 7    # scratch + full provisioning
    assert spec.pages_for(1) == 1 and spec.pages_for(16) == 1
    assert spec.pages_for(17) == 2
