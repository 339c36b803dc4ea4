"""Transformer/Mamba block: init, cache init, and apply for all layer kinds."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import LayerSpec, ModelConfig
from repro.models.attention import (apply_attention, init_attention,
                                    init_kv_cache, init_paged_kv_cache)
from repro.models.mla import apply_mla, init_mla, init_mla_cache, \
    init_paged_mla_cache
from repro.models.mamba2 import apply_mamba, init_mamba, init_mamba_cache
from repro.models.mlp_moe import apply_mlp, apply_moe, init_mlp, init_moe
from repro.models.norms import apply_norm, init_norm
from repro.serve.kvcache import PageSpec


def init_block(cfg: ModelConfig, spec: LayerSpec, key) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": init_norm(cfg, d)}
    if spec.kind == "attn":
        if cfg.attention == "mla":
            p["attn"] = init_mla(cfg, ks[0])
        else:
            p["attn"] = init_attention(cfg, ks[0])
        if spec.cross_attn:
            p["lnx"] = init_norm(cfg, d)
            p["xattn"] = init_attention(cfg, ks[1], cross=True)
    else:
        p["mamba"] = init_mamba(cfg, ks[0])
    if spec.mlp == "dense":
        p["ln2"] = init_norm(cfg, d)
        p["mlp"] = init_mlp(cfg, ks[2], cfg.d_ff)
    elif spec.mlp == "moe":
        p["ln2"] = init_norm(cfg, d)
        p["moe"] = init_moe(cfg, ks[2])
    return p


def init_block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, enc_len: int = 0,
                     paged: Optional[PageSpec] = None) -> dict:
    """`paged`: build page-pool caches for continuous batching; `batch` is
    then the slot count (Mamba state caches stay slot-indexed, unpaged)."""
    c: dict = {}
    if spec.kind == "attn":
        if paged is not None:
            if spec.cross_attn:
                raise NotImplementedError(
                    "paged serving does not cover enc-dec cross-attention")
            c["attn"] = (init_paged_mla_cache(cfg, paged)
                         if cfg.attention == "mla"
                         else init_paged_kv_cache(cfg, paged))
            return c
        if cfg.attention == "mla":
            c["attn"] = init_mla_cache(cfg, batch, max_len)
        else:
            c["attn"] = init_kv_cache(cfg, batch, max_len,
                                      window=cfg.attn_window)
        if spec.cross_attn:
            kvh, hd = cfg.n_kv_heads, cfg.hd
            c["xattn"] = {
                "k": jnp.zeros((batch, enc_len, kvh, hd), cfg.adtype),
                "v": jnp.zeros((batch, enc_len, kvh, hd), cfg.adtype),
                "pos": jnp.full((batch, enc_len), -1, jnp.int32),
            }
    else:
        c["mamba"] = init_mamba_cache(cfg, batch)
    return c


def apply_block(cfg: ModelConfig, spec: LayerSpec, p: dict, x: jax.Array, *,
                positions: jax.Array, mode: str = "train",
                cache: Optional[dict] = None,
                enc_out: Optional[jax.Array] = None,
                paged: Optional[dict] = None,
                taps: Optional[dict] = None, tap_prefix: str = ""):
    """Returns (y, new_cache, aux). mode: train|encode|prefill|decode.
    `paged` carries block-table indices for paged caches (serve/kvcache.py).
    aux["loss"] is the block's auxiliary loss, aux["load"] (n, 3) int32 the
    routing counts of its n (0 or 1) MoE calls (see `apply_moe`)."""
    causal = mode != "encode"
    decode = mode == "decode"
    new_cache: dict = dict(cache) if cache is not None else None
    aux = {"loss": jnp.zeros((), jnp.float32),
           "load": jnp.zeros((0, 3), jnp.int32)}

    h = apply_norm(cfg, p["ln1"], x)
    if spec.kind == "attn":
        if cfg.attention == "mla":
            y, nc = apply_mla(cfg, p["attn"], h, positions=positions,
                              cache=None if cache is None else cache["attn"],
                              decode=decode, paged=paged, taps=taps,
                              tap_prefix=tap_prefix + "attn/")
        else:
            y, nc = apply_attention(
                cfg, p["attn"], h, positions=positions, causal=causal,
                window=cfg.attn_window,
                cache=None if cache is None else cache["attn"],
                paged=paged, taps=taps, tap_prefix=tap_prefix + "attn/")
        if new_cache is not None and nc is not None:
            new_cache["attn"] = nc
    else:
        y, nc = apply_mamba(cfg, p["mamba"], h,
                            cache=None if cache is None else cache["mamba"],
                            decode=decode, positions=positions,
                            slot=None if paged is None else paged.get("slots"),
                            taps=taps, tap_prefix=tap_prefix + "mamba/")
        if new_cache is not None and nc is not None:
            new_cache["mamba"] = nc
    x = x + y

    if spec.cross_attn:
        hx = apply_norm(cfg, p["lnx"], x)
        xc = None if cache is None else cache.get("xattn")
        kv_src = enc_out
        kv_positions = None
        if enc_out is not None:
            kv_positions = jnp.broadcast_to(
                jnp.arange(enc_out.shape[1], dtype=jnp.int32)[None, :],
                (enc_out.shape[0], enc_out.shape[1]))
        y, ncx = apply_attention(
            cfg, p["xattn"], hx, positions=positions, causal=False,
            cache=xc, kv_src=kv_src, kv_positions=kv_positions,
            rope_variant="none", taps=taps, tap_prefix=tap_prefix + "xattn/")
        if new_cache is not None and ncx is not None:
            new_cache["xattn"] = ncx
        x = x + y

    if spec.mlp == "dense":
        h2 = apply_norm(cfg, p["ln2"], x)
        x = x + apply_mlp(cfg, p["mlp"], h2, taps, tap_prefix + "mlp/")
    elif spec.mlp == "moe":
        h2 = apply_norm(cfg, p["ln2"], x)
        # paged serving carries junk tokens that must route to no expert
        # (no wasted expert rows, and no rows in the routing counts):
        # left-padding in prefill (pos = -1) and idle slots in decode
        # (kv_len == 0). Unpaged modes never do — pass None so the
        # shard_map MoE fast path stays available to them.
        if paged is not None and mode == "prefill":
            moe_valid = positions >= 0
        elif paged is not None and mode == "decode" and "kv_len" in paged:
            moe_valid = (paged["kv_len"] > 0)[:, None]
        else:
            moe_valid = None
        y2, moe_aux = apply_moe(cfg, p["moe"], h2, taps,
                                tap_prefix + "moe/", valid=moe_valid)
        aux = {"loss": moe_aux["loss"], "load": moe_aux["load"][None]}
        x = x + y2
    return x, new_cache, aux
