"""Multi-head Latent Attention (DeepSeek-V2) with compressed KV cache.

Train/prefill use the non-absorbed form (materialize K/V from the latent,
chunked flash attention); a chunked or prefix-sharing prefill materializes
them from the latent context it reads back through the block table. Decode
uses the *absorbed* form: queries are projected into the latent space and
attention runs directly against the cached (c_kv, k_rope) — the
deployment-relevant O(r + rope) cache per token. Both forms scale scores
by 1/sqrt(qk head dim) times YaRN's mscale(factor, mscale_all_dim)^2 when
the config extends its context (`rope_scaling`).

The latent RMSNorm ("kvnorm") is a tweakable norm for the paper's pipeline.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import TP_AXIS, lc
from repro.models.config import ModelConfig
from repro.models.attention import attention_core, _cache_write, _paged_update
from repro.models.linear import dense, init_dense, materialize
from repro.models.norms import apply_norm, init_norm
from repro.models.rope import apply_rope, yarn_mscale
from repro.serve.kvcache import PageSpec


def init_mla(cfg: ModelConfig, key) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d, h * qk, dtype=cfg.pdtype),
        "wdkv": init_dense(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim,
                           dtype=cfg.pdtype),
        "kvnorm": init_norm(cfg, m.kv_lora_rank),
        "wukv": init_dense(ks[2], m.kv_lora_rank,
                           h * (m.qk_nope_head_dim + m.v_head_dim),
                           dtype=cfg.pdtype),
        "wo": init_dense(ks[3], h * m.v_head_dim, d, dtype=cfg.pdtype),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    m = cfg.mla
    return {
        "k": jnp.zeros((batch, max_len, 1, m.kv_lora_rank), cfg.adtype),   # c_kv
        "v": jnp.zeros((batch, max_len, 1, m.qk_rope_head_dim), cfg.adtype),  # k_pe
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def init_paged_mla_cache(cfg: ModelConfig, spec: PageSpec) -> dict:
    """Page-pool latent cache (c_kv under "k_pool", k_pe under "v_pool")."""
    m = cfg.mla
    return {
        "k_pool": jnp.zeros((spec.n_pages, spec.page_size, 1, m.kv_lora_rank),
                            cfg.adtype),
        "v_pool": jnp.zeros((spec.n_pages, spec.page_size, 1,
                             m.qk_rope_head_dim), cfg.adtype),
    }


def _project_latent(cfg, p, x, positions):
    """Returns (c_kv normed, k_pe roped): (B,S,r), (B,S,rope)."""
    m = cfg.mla
    ckv_kpe = dense(p["wdkv"], x)
    c_kv, k_pe = jnp.split(ckv_kpe, [m.kv_lora_rank], axis=-1)
    c_kv = apply_norm(cfg, p["kvnorm"], c_kv)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, theta=cfg.rope_theta,
                      variant="full", scaling=cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_pe


def softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head dim), times mscale(factor, mscale_all_dim)^2 under
    YaRN, as DeepSeek-V2 scales its scores."""
    m = cfg.mla
    scale = 1.0 / ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5)
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _queries(cfg, p, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = dense(p["wq"], x).reshape(b, s, h, qk)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = apply_rope(q_pe, positions, theta=cfg.rope_theta, variant="full",
                      scaling=cfg.rope_scaling)
    return q_nope, q_pe


def apply_mla(cfg: ModelConfig, p: dict, x: jax.Array, *,
              positions: jax.Array, cache: Optional[dict] = None,
              decode: bool = False, paged: Optional[dict] = None,
              taps: Optional[dict] = None, tap_prefix: str = ""):
    """Returns (y, new_cache)."""
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads

    if taps is not None:
        taps[tap_prefix + "wq"] = x
        taps[tap_prefix + "wdkv"] = x

    q_nope, q_pe = _queries(cfg, p, x, positions)
    c_kv, k_pe = _project_latent(cfg, p, x, positions)
    if taps is not None:
        taps[tap_prefix + "wukv"] = c_kv

    new_cache = cache
    paged_view = None
    if cache is not None and "k_pool" in cache:
        new_cache, paged_view = _paged_update(
            cache, c_kv[:, :, None, :], k_pe[:, :, None, :], positions, paged)
    elif cache is not None:
        new_cache = _cache_write(cache, c_kv[:, :, None, :], k_pe[:, :, None, :],
                                 positions)

    if decode:
        assert cache is not None
        if paged_view is not None:
            ckv_g, kpe_g, kv_pos = paged_view
            ckv_all = ckv_g[:, :, 0, :]                          # (B, T, r)
            kpe_all = kpe_g[:, :, 0, :]                          # (B, T, rope)
        else:
            ckv_all = new_cache["k"][:, :, 0, :]                 # (B, T, r)
            kpe_all = new_cache["v"][:, :, 0, :]                 # (B, T, rope)
            kv_pos = new_cache["pos"]
        wukv = materialize(p["wukv"]["w"], jnp.float32).reshape(
            m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
        wuk, wuv = wukv[:, :, :m.qk_nope_head_dim], wukv[:, :, m.qk_nope_head_dim:]
        # absorb: q_latent = q_nope @ W_uk  -> (B, S, H, r)
        ql = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32), wuk)
        scale = softmax_scale(cfg)
        sc = (jnp.einsum("bshr,btr->bhst", ql, ckv_all.astype(jnp.float32)) +
              jnp.einsum("bshp,btp->bhst", q_pe.astype(jnp.float32),
                         kpe_all.astype(jnp.float32))) * scale
        msk = (kv_pos[:, None, :] >= 0) & \
              (kv_pos[:, None, :] <= positions[:, :, None])       # (B,S,T)
        sc = jnp.where(msk[:, None, :, :], sc, -1e30)             # (B,H,S,T)
        probs = jax.nn.softmax(sc, axis=-1)                      # (B,H,S,T)
        ctx = jnp.einsum("bhst,btr->bshr", probs, ckv_all.astype(jnp.float32))
        o = jnp.einsum("bshr,rhv->bshv", ctx, wuv)               # (B,S,H,v)
        o = o.astype(x.dtype).reshape(b, s, h * m.v_head_dim)
    else:
        # non-absorbed: materialize per-head K/V (MHA), chunked attention.
        # A chunked / prefix-suffix prefill (paged "kv_len") attends to the
        # whole latent context read back through its block-table rows —
        # earlier chunks and shared prefix pages included; a fresh prompt
        # attends to its own latents
        kv_pos = positions
        if paged_view is not None and "kv_len" in paged:
            ckv_g, kpe_g, kv_pos = paged_view
            c_kv, k_pe = ckv_g[:, :, 0, :], kpe_g[:, :, 0, :]
        t = c_kv.shape[1]
        kv = dense(p["wukv"], c_kv).reshape(
            b, t, h, m.qk_nope_head_dim + m.v_head_dim)
        k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                      (b, t, h, m.qk_rope_head_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        q = lc(q, "batch", "seq", "heads", "qk_dim")
        k = lc(k, "batch", "kv_seq", "heads", "qk_dim")
        v = lc(v, "batch", "kv_seq", "heads", "head_dim")
        o = attention_core(q, k, v, q_pos=positions, kv_pos=kv_pos,
                           causal=True, block_kv=cfg.attn_block_kv,
                           scale=softmax_scale(cfg))
        o = o.reshape(b, s, h * m.v_head_dim)

    if taps is not None:
        taps[tap_prefix + "wo"] = o
    # serving TP: wq/wukv are head-column-parallel, the latent projection
    # wdkv is replicated (per-token latent, no head dim), and wo is
    # row-parallel over the local heads' value slice
    y = dense(p["wo"], o, reduce_axis=TP_AXIS if cfg.tp > 1 else None)
    return lc(y, "batch", "seq", "embed"), new_cache
