"""Dropless MoE routing, YaRN latent attention and the engine's MoE
counters: `apply_moe` against a per-token loop, YaRN's frequencies and
softmax factor against the published closed forms, co-batched MoE prefill
against one request at a time, and the routing counts against a count
taken from the routing indices."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TINY, get_smoke_config
from repro.models.config import MoEConfig, RopeScaling
from repro.models.mlp_moe import apply_moe, init_moe
from repro.models.transformer import init_lm
from repro.serve import engine as engine_mod
from repro.serve.engine import ContinuousEngine

CFG = TINY.replace(d_model=32, dtype="float32", param_dtype="float32")


def _routing(cfg, p, xf):
    probs = jax.nn.softmax(xf @ p["router"]["w"], axis=-1)
    return jax.lax.top_k(probs, cfg.moe.top_k)


def _per_token(cfg, p, xf):
    """Each token alone: its top-k experts, gates as the config says, the
    routed SwiGLUs summed, plus the shared experts."""
    m, ex = cfg.moe, p["experts"]
    gate, idx = _routing(cfg, p, xf)
    if m.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    out = []
    for t in range(xf.shape[0]):
        y = jnp.zeros(xf.shape[1])
        for j in range(m.top_k):
            e = int(idx[t, j])
            h = (jax.nn.silu(xf[t] @ ex["wg"]["w"][e])
                 * (xf[t] @ ex["wi"]["w"][e]))
            y = y + gate[t, j] * (h @ ex["wo"]["w"][e])
        if "shared" in p:
            sh = p["shared"]
            y = y + (jax.nn.silu(xf[t] @ sh["wg"]["w"])
                     * (xf[t] @ sh["wi"]["w"])) @ sh["wo"]["w"]
        out.append(y)
    return jnp.stack(out)


@pytest.mark.parametrize("norm,shared", [
    (True, 0),          # Mixtral: renormalised top-k gates
    (False, 2),         # DeepSeek-V2: softmax probabilities as gates
    (True, 1),          # renormalised gates beside a shared expert
])
def test_dropless_moe_matches_a_per_token_loop(norm, shared):
    cfg = CFG.replace(moe=MoEConfig(n_experts=8, top_k=3, n_shared=shared,
                                    d_ff_expert=16, norm_topk_prob=norm))
    p = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    y, aux = apply_moe(cfg, p, x)
    want = _per_token(cfg, p, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    assert int(aux["load"][0]) == 18 * 3


@pytest.mark.parametrize("valid", [False, True])
def test_moe_load_counts_the_routing_indices(valid):
    """aux["load"] = [rows routed, experts that got rows, the busiest
    expert's rows], counted here from the router's own top-k indices of
    the real tokens."""
    cfg = CFG.replace(moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=16))
    p = init_moe(cfg, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 11, cfg.d_model))
    mask = (np.random.default_rng(0).random((3, 11)) < 0.6 if valid
            else np.ones((3, 11), bool))
    _, aux = apply_moe(cfg, p, x, valid=jnp.asarray(mask) if valid else None)
    _, idx = _routing(cfg, p, x.reshape(-1, cfg.d_model))
    counts = np.bincount(np.asarray(idx)[mask.reshape(-1)].reshape(-1),
                         minlength=8)
    assert np.asarray(aux["load"]).tolist() == [
        counts.sum(), (counts > 0).sum(), counts.max()]


# ------------------------------------------------------------------ YaRN

def _published_yarn(dim, theta, factor, max_pos, fast, slow):
    """DeepSeek-V2's DeepseekV2YarnRotaryEmbedding inverse frequencies,
    in float64."""
    def corr(rot):
        return dim * math.log(max_pos / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * theta ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask, (low, high)


def test_yarn_frequencies_match_the_published_formula():
    from repro.models.rope import rope_frequencies

    rs = get_smoke_config("deepseek-v2-lite-16b").rope_scaling
    want, (low, high) = _published_yarn(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    assert (low, high) == (10, 23)
    got = rope_frequencies(64, 10000.0, rs)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # below the ramp the frequency is kept, past it divided by 40
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        want[-1]) and want[-1] == pytest.approx(
            10000.0 ** (-62 / 64) / 40.0)


@pytest.mark.parametrize("mscale,mscale_all,cos_scale", [
    (0.707, 0.707, 1.0),                   # DeepSeek-V2-Lite
    (1.0, 0.707, (0.1 * math.log(40) + 1) / (0.0707 * math.log(40) + 1)),
])
def test_yarn_mscale_matches_the_published_formula(mscale, mscale_all,
                                                  cos_scale):
    from repro.models.mla import softmax_scale
    from repro.models.rope import apply_rope, rope_cos_scale, yarn_mscale

    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.26081, abs=1e-5)
    assert yarn_mscale(1.0, 0.707) == 1.0
    rs = RopeScaling(mscale=mscale, mscale_all_dim=mscale_all)
    assert rope_cos_scale(rs) == pytest.approx(cos_scale)
    cfg = get_smoke_config("deepseek-v2-lite-16b").replace(rope_scaling=rs)
    qk = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
    assert softmax_scale(cfg) == pytest.approx(
        yarn_mscale(40.0, mscale_all) ** 2 / math.sqrt(qk))
    # at position 0 the rotation is the identity, scaled by cos_scale
    x = jnp.ones((1, 1, 1, 8))
    y = apply_rope(x, jnp.zeros((1, 1), jnp.int32), scaling=rs)
    np.testing.assert_allclose(np.asarray(y), cos_scale, rtol=1e-6)


# ----------------------------------------------------- the serving engine

def _serve(cfg, params, prompts, **kw):
    eng = ContinuousEngine(cfg, params, n_slots=4, max_len=64, page_size=8,
                           prefill_bucket=8, **kw)
    for prompt in prompts:
        eng.submit(prompt, max_new=5)
    return eng, [r.tokens for r in eng.run(max_steps=500)]


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "mixtral-8x22b"])
def test_co_batched_moe_prefill_is_token_identical(arch):
    """Four prompts of one bucket prefilled in one call (prefill_batch 4)
    decode the same greedy tokens as one prompt a call: dropless routing
    makes a token's experts independent of its call's other tokens."""
    cfg = get_smoke_config(arch)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (9, 12, 14, 16)]
    eng4, got = _serve(cfg, params, prompts, prefill_batch=4)
    eng1, want = _serve(cfg, params, prompts, prefill_batch=1)
    assert got == want
    assert eng4.n_prefills == 1 and eng1.n_prefills == 4


@pytest.mark.parametrize("arch,tp", [
    ("deepseek-v2-lite-16b", 1),
    ("dense", 1),
    pytest.param("dense", 2, marks=pytest.mark.skipif(
        len(jax.devices()) < 2, reason="TP needs 2 local devices (run with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=2)")),
])
def test_prefill_alone_warms_every_co_batched_shape(arch, tp):
    """With precompile_prefill, the first prefill at a bucket compiles the
    bucket's pow2 batch sizes up to prefill_batch, so once one request
    alone has prefilled in a bucket, four prompts co-batched in it
    compile nothing, on one device or under TP."""
    cfg = (TINY.replace(n_repeats=2, d_model=64, head_dim=16, d_ff=128)
           if arch == "dense" else get_smoke_config(arch))
    params = init_lm(cfg, jax.random.PRNGKey(2))
    eng = ContinuousEngine(cfg, params, n_slots=4, max_len=64, page_size=8,
                           prefill_bucket=8, prefill_batch=4,
                           precompile_prefill=True, tp=tp)
    rng = np.random.default_rng(7)
    eng.submit(rng.integers(0, cfg.vocab_size, 12), max_new=1)
    eng.run(max_steps=100)
    compiles, on = [], [True]

    def listen(name, secs, **_):
        if on[0] and name == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for n in (9, 10, 14, 16):
            eng.submit(rng.integers(0, cfg.vocab_size, n), max_new=1)
        done = eng.run(max_steps=100)
    finally:
        on[0] = False
    assert [len(r.tokens) for r in done] == [1] * 4
    assert eng.n_prefills == 2 and compiles == []


def test_engine_moe_counters_count_the_routed_rows(monkeypatch):
    """The engine's MoE counters, fetched with the tokens: rows routed are
    top_k per real token per MoE layer (prompt tokens, and every slot
    decoding at a block's start for each of its steps), each prefill call
    and decode block is one MOE_LOG entry, and the counters are the
    entries' sums."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    params = init_lm(cfg, jax.random.PRNGKey(1))
    blocks = []
    decode_block = ContinuousEngine._decode_block

    def spy(self, now):
        active, steps = int(self.active.sum()), self.n_decode_steps
        out = decode_block(self, now)
        blocks.append(active * (self.n_decode_steps - steps))
        return out

    monkeypatch.setattr(ContinuousEngine, "_decode_block", spy)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 11, 16)]
    eng, _ = _serve(cfg, params, prompts)
    n_moe, k = cfg.n_repeats, cfg.moe.top_k
    assert eng.n_moe_rows == k * n_moe * (eng.n_prefill_tokens + sum(blocks))
    log = [e for e in engine_mod.MOE_LOG if e[0] == eng.serial]
    assert len(log) == eng.n_prefills + len(blocks)
    assert [sum(e[i] for e in log) for i in (3, 4, 5, 6)] == [
        eng.n_moe_calls, eng.n_moe_rows, eng.n_moe_experts_hit,
        eng.n_moe_busiest_rows]
    assert eng.n_moe_calls == n_moe * (eng.n_prefills
                                       + eng.n_decode_steps)
    assert eng.n_moe_experts_hit <= cfg.moe.n_experts * eng.n_moe_calls
    assert eng.n_moe_busiest_rows * cfg.moe.n_experts >= eng.n_moe_rows
