"""Rotary position embeddings: full (llama), half ("2d", ChatGLM) variants,
with optional YaRN frequency scaling (DeepSeek-V2)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1,
    or 1 where the context is not extended."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, dim: int, theta: float,
                    max_pos: int) -> float:
    """The rotary dim whose wavelength fits `rotations` turns in max_pos."""
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_frequencies(dim: int, theta: float, scaling=None) -> np.ndarray:
    """(dim // 2,) float32 inverse frequencies theta^(-2i/dim); with a
    `RopeScaling`, YaRN's blend as DeepSeek-V2's published code computes
    it: frequencies / factor below the correction range (long
    wavelengths), unchanged above it, and a linear ramp between."""
    half = dim // 2
    extra = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2 / dim)
    if scaling is None:
        return extra.astype(np.float32)
    inter = extra / scaling.factor
    max_pos = scaling.original_max_position_embeddings
    low = max(math.floor(_correction_dim(scaling.beta_fast, dim, theta,
                                         max_pos)), 0)
    high = min(math.ceil(_correction_dim(scaling.beta_slow, dim, theta,
                                         max_pos)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp            # 1: keep the original frequency
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_cos_scale(scaling) -> float:
    """The factor YaRN puts on cos and sin: mscale(f, mscale) /
    mscale(f, mscale_all_dim) (1 without scaling)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, *, theta: float = 10000.0,
               variant: str = "full", scaling=None) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32. Rotate-half convention.

    variant "full": rotate all head dims; "half": rotate only the first
    hd//2 dims (ChatGLM's 2D RoPE applies rotary to half the channels);
    "none": identity. `scaling`: a `RopeScaling` (YaRN) or None.
    """
    if variant == "none":
        return x
    hd = x.shape[-1]
    rot = hd if variant == "full" else hd // 2
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    if scaling is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(rope_frequencies(rot, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * freqs       # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]                            # (B, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    m = rope_cos_scale(scaling)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    x1, x2 = xr[..., :half].astype(jnp.float32), xr[..., half:].astype(jnp.float32)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = jnp.concatenate([rotated.astype(x.dtype), xp], axis=-1)
    return out


def sinusoidal_positions(seq_len: int, d_model: int, dtype=jnp.float32) -> jax.Array:
    """Whisper-style fixed sinusoidal table (seq_len, d_model)."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / (half - 1))
    ang = jnp.arange(seq_len)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)
