"""Pallas TPU kernels: grouped (dropless) expert dequant matmuls.

The MoE serving hot-spot. Dropless routing (models/mlp_moe.py) sorts a
call's (token, expert) pairs by expert and pads each expert's group to
whole row tiles, so every expert's rows are contiguous and row tile i
belongs to one expert, `tile_expert[i]`. These kernels multiply each row
tile by that expert's packed (K, N) slab, read straight from the stacked
(E, packed_rows(K), N) layout: an expert that received no rows has no
tile, so its weights are never read, and a call streams each hit
expert's weights once per row tile it holds. The row buffer has a static
bound (T·k + E·(bm − 1) rows, rounded to tiles); the tiles past the live
count are skipped without a DMA.

Template instances of `kernels/template.py` with `grouped=True`:
  * `grouped_expert_dequant_pallas` — epilogue "dequant_bf16" (W2/W3/W4/W8
    weight-only serving);
  * `grouped_expert_w8a8_pallas` — epilogue "int8_mxu" (W4A8/W8A8: int8
    rows quantized per token by the caller, which also applies their
    scales).
Grid: (row tiles, N/bn, K/bk), K innermost; the tile -> expert map and
the live tile count are scalar-prefetched so the index maps pick each
weight block before its DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.template import (MatmulSpec, grouped_grid_spec,
                                    make_matmul_kernel, scale_operand)

_DEQUANT = MatmulSpec("grouped_expert_dequant", epilogue="dequant_bf16",
                      grouped=True)
_W8A8 = MatmulSpec("grouped_expert_w8a8", epilogue="int8_mxu", grouped=True)


def _dims(x, qw, scale, bm, bn, bk):
    r, k = x.shape
    n = qw.shape[-1]
    assert r % bm == 0 and k % bk == 0 and n % bn == 0, (r, k, n, bm, bn, bk)
    return dict(n_tiles=r // bm, n=n, k=k, g=scale.shape[-2], bm=bm, bn=bn,
                bk=bk)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm", "bn",
                                             "bk", "interpret"))
def grouped_expert_dequant_pallas(x: jax.Array, qw: jax.Array,
                                  scale: jax.Array, tile_expert: jax.Array,
                                  n_live: jax.Array, *, bits: int,
                                  group_size: int, bm: int, bn: int, bk: int,
                                  interpret: bool = False) -> jax.Array:
    """x: (R, K) rows in expert groups of whole bm-row tiles; qw:
    (E, packed_rows(K), N) uint8; scale: (E, G, N); tile_expert: (R/bm,)
    int32; n_live: (1,) int32 live tiles. Returns (R, N) f32, zero on
    dead tiles."""
    d = _dims(x, qw, scale, bm, bn, bk)
    return pl.pallas_call(
        make_matmul_kernel(_DEQUANT, bits=bits, bk=bk),
        grid_spec=grouped_grid_spec(_DEQUANT, bits=bits, **d),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], d["n"]), jnp.float32),
        interpret=interpret,
        name="grouped_expert_dequant_pallas",
    )(tile_expert, n_live, x, qw, scale_operand(scale, k=d["k"], bk=bk))


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm", "bn",
                                             "bk", "interpret"))
def grouped_expert_w8a8_pallas(xq: jax.Array, qw: jax.Array,
                               scale: jax.Array, tile_expert: jax.Array,
                               n_live: jax.Array, *, bits: int,
                               group_size: int, bm: int, bn: int, bk: int,
                               interpret: bool = False) -> jax.Array:
    """xq: (R, K) int8 rows in expert groups; the rest as
    `grouped_expert_dequant_pallas`. Returns (R, N) f32 *before* the
    per-row activation rescale."""
    d = _dims(xq, qw, scale, bm, bn, bk)
    gs = group_size if group_size != -1 else d["k"]
    assert (gs >= bk and gs % bk == 0) or (gs < bk and bk % gs == 0)
    return pl.pallas_call(
        make_matmul_kernel(_W8A8, bits=bits, bk=bk),
        grid_spec=grouped_grid_spec(_W8A8, bits=bits, **d),
        out_shape=jax.ShapeDtypeStruct((xq.shape[0], d["n"]), jnp.float32),
        interpret=interpret,
        name="grouped_expert_w8a8_pallas",
    )(tile_expert, n_live, xq, qw, scale_operand(scale, k=d["k"], bk=bk))
