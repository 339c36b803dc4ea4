"""Pallas TPU kernel: RTN quantize + pack (offline/deploy-time path).

Rounds a (K, N) float weight tile to the symmetric grid and packs
offset-binary values along K in `pack_layout(bits)` groups (one byte for
2/4/8-bit, a 3-byte/8-value word for 3-bit), writing packed uint8 tiles.
Keeps the whole quantize->pack in VMEM (no int staging in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant.types import pack_layout, qmax_for_bits
from repro.kernels.template import (packed_tile_rows, scale_blockspec,
                                    scale_operand)


def _quantize_kernel(w_ref, scale_ref, o_ref, *, bits: int, bk: int):
    w = w_ref[...].astype(jnp.float32)                 # (bk, bn)
    s = scale_ref[0]                                   # (gb, bn)
    gb, bn = s.shape
    qmax = qmax_for_bits(bits)
    ws = (w.reshape(gb, bk // gb, bn) / s[:, None, :]).reshape(bk, bn)
    q = jnp.clip(jnp.round(ws), -qmax, qmax).astype(jnp.int32)
    bpg, vpg = pack_layout(bits)
    if (bpg, vpg) == (1, 1):
        o_ref[...] = (q + qmax).astype(jnp.uint8)
        return
    u = (q + qmax).astype(jnp.uint32).reshape(bk // vpg, vpg, bn)
    word = jnp.zeros((bk // vpg, bn), jnp.uint32)
    for i in range(vpg):
        word = word | (u[:, i, :] << (bits * i))
    if bpg == 1:
        o_ref[...] = word.astype(jnp.uint8)
    else:
        # multi-byte group (W3): emit the word little-endian along K
        out = jnp.stack([(word >> (8 * b)) & 0xFF for b in range(bpg)],
                        axis=1)
        o_ref[...] = out.reshape(bk // vpg * bpg, bn).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bk", "bn",
                                             "interpret"))
def quantize_pack_pallas(w: jax.Array, scale: jax.Array, *, bits: int,
                         group_size: int, bk: int = 256, bn: int = 256,
                         interpret: bool = False) -> jax.Array:
    """w: (K, N); scale: (G, N). Returns packed uint8 (packed_rows(K), N)."""
    k, n = w.shape
    g = scale.shape[0]
    vpg = pack_layout(bits)[1]
    bk = min(bk, k)
    bn = min(bn, n)
    assert k % bk == 0 and n % bn == 0 and bk % vpg == 0

    # reuse the dequant scale indexing, adding a dummy leading grid dim
    sspec = scale_blockspec(k, g, bk, bn)
    sspec2 = pl.BlockSpec(sspec.block_shape,
                          lambda kk, j: sspec.index_map(0, j, kk))

    kernel = functools.partial(_quantize_kernel, bits=bits, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(k // bk, n // bn),
        in_specs=[pl.BlockSpec((bk, bn), lambda kk, j: (kk, j)), sspec2],
        out_specs=pl.BlockSpec((packed_tile_rows(bk, bits), bn),
                               lambda kk, j: (kk, j)),
        out_shape=jax.ShapeDtypeStruct((packed_tile_rows(k, bits), n),
                                       jnp.uint8),
        interpret=interpret,
        name="quantize_pack_pallas",
    )(w, scale_operand(scale, k=k, bk=bk))
