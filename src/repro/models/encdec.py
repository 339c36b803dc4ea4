"""Encoder-decoder LM (Whisper backbone). Frontend conv is a stub: the
encoder consumes precomputed frame embeddings (B, S_enc, d_model)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.norms import apply_norm, init_norm
from repro.models.rope import sinusoidal_positions
from repro.models.transformer import (_head, _run_stack, init_cache, init_lm,
                                      _embed)
from repro.models.blocks import init_block
from repro.utils.tree import tree_stack


def enc_config(cfg: ModelConfig) -> ModelConfig:
    return cfg.replace(pattern=cfg.enc_pattern, n_repeats=cfg.n_enc_repeats,
                       prefix_pattern=(), enc_dec=False, pos_emb="none",
                       attn_window=None, frontend="none")


def dec_config(cfg: ModelConfig) -> ModelConfig:
    return cfg.replace(enc_dec=False, frontend="none")


def init_encdec(cfg: ModelConfig, key) -> dict:
    ke, kd = jax.random.split(key)
    ecfg = enc_config(cfg)
    enc = {"final_norm": init_norm(ecfg, ecfg.d_model), "stack": {}}
    ks = jax.random.split(ke, len(ecfg.pattern))
    for j, spec in enumerate(ecfg.pattern):
        reps = [init_block(ecfg, spec, kk)
                for kk in jax.random.split(ks[j], ecfg.n_repeats)]
        enc["stack"][f"p{j}"] = tree_stack(reps)
    dec = init_lm(dec_config(cfg), kd)
    return {"enc": enc, "dec": dec}


def encode(cfg: ModelConfig, params: dict, frames: jax.Array) -> jax.Array:
    """frames: (B, S_enc, d) precomputed embeddings (conv frontend stub)."""
    ecfg = enc_config(cfg)
    b, s, d = frames.shape
    x = frames.astype(ecfg.adtype) + sinusoidal_positions(s, d, ecfg.adtype)[None]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _, _, _ = _run_stack(ecfg, params["enc"], x, positions=positions,
                            mode="encode", cache=None)
    return apply_norm(ecfg, params["enc"]["final_norm"], x)


def encdec_forward(cfg: ModelConfig, params: dict, frames: jax.Array,
                   tokens: jax.Array):
    """Teacher-forced forward. Returns (dec logits f32, aux)."""
    enc_out = encode(cfg, params, frames)
    dcfg = dec_config(cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed(dcfg, params["dec"], tokens, None, positions)
    x, _, aux, _ = _run_stack(dcfg, params["dec"], x, positions=positions,
                              mode="train", cache=None, enc_out=enc_out)
    return _head(dcfg, params["dec"], x), aux


def encdec_loss(cfg: ModelConfig, params: dict, batch: dict):
    logits, aux = encdec_forward(cfg, params, batch["frames"], batch["tokens"])
    labels = batch["labels"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    mask = batch.get("mask", jnp.ones_like(nll))
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux, {"nll": loss, "aux": aux}


def encdec_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                      enc_len: int) -> dict:
    return init_cache(dec_config(cfg), batch, max_len, enc_len=enc_len)


def encdec_prefill(cfg: ModelConfig, params: dict, frames: jax.Array,
                   tokens: jax.Array, cache: dict):
    """Encode audio + ingest decoder prompt. Returns (logits (B,V), cache)."""
    enc_out = encode(cfg, params, frames)
    dcfg = dec_config(cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed(dcfg, params["dec"], tokens, None, positions)
    x, new_cache, _, _ = _run_stack(dcfg, params["dec"], x,
                                    positions=positions, mode="prefill",
                                    cache=cache, enc_out=enc_out)
    logits = _head(dcfg, params["dec"], x[:, -1:, :])
    return logits[:, 0, :], new_cache


def encdec_decode(cfg: ModelConfig, params: dict, tokens: jax.Array,
                  cache: dict, positions: jax.Array):
    """One decoder step against cached self+cross K/V."""
    dcfg = dec_config(cfg)
    x = _embed(dcfg, params["dec"], tokens, None, positions)
    x, new_cache, _, _ = _run_stack(dcfg, params["dec"], x,
                                    positions=positions, mode="decode",
                                    cache=cache, enc_out=None)
    logits = _head(dcfg, params["dec"], x)
    return logits[:, 0, :], new_cache
