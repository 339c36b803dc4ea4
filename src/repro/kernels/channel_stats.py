"""Pallas TPU kernel: fused per-channel sum / sum-of-squares reduction.

Feeds the paper's channel-wise distribution loss (Eq. 2): one pass over the
activation tensor (T, C) accumulates per-channel first and second moments —
bandwidth-bound, so fusing both moments halves HBM traffic vs two jnp
reductions. Grid: (C/bc, T/bt) with T innermost, accumulating into the
(1, bc) output tiles held in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stats_kernel(x_ref, sum_ref, sq_ref):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    xb = x_ref[...].astype(jnp.float32)                # (bt, bc)
    sum_ref[...] += jnp.sum(xb, axis=0, keepdims=True)
    sq_ref[...] += jnp.sum(xb * xb, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bt", "bc", "interpret"))
def channel_stats_pallas(x: jax.Array, *, bt: int = 256, bc: int = 256,
                         interpret: bool = False):
    """x: (T, C) -> (mean (C,), var (C,)) in f32."""
    t, c = x.shape
    bt = min(bt, t)
    bc = min(bc, c)
    assert t % bt == 0 and c % bc == 0, (t, c, bt, bc)
    grid = (c // bc, t // bt)
    s, sq = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bt, bc), lambda j, i: (i, j))],
        out_specs=[pl.BlockSpec((1, bc), lambda j, i: (0, j)),
                   pl.BlockSpec((1, bc), lambda j, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        interpret=interpret,
        name="channel_stats_pallas",
    )(x)
    mean = s[0] / t
    var = sq[0] / t - mean * mean
    return mean, jnp.maximum(var, 0.0)
