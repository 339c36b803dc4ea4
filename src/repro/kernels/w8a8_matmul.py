"""Pallas TPU kernel: true W8A8 / W4A8 int8 MXU matmul (FPTQ-style).

Replaces the fake-quant-then-bf16 detour that `act_bits=8` used to take:
activations are dynamically quantized to int8 with a per-token scale
(`quantize_activation` in core/quant/types.py), packed weights are unpacked
to int8 values in VREGs, and each scale group runs one
int8 x int8 -> int32 MXU dot. The int32 partials are rescaled per group by
the weight scale and accumulated in an f32 VMEM tile; the per-token
activation scale is a rank-1 rescale applied by the caller (kernels/ops.py)
so the kernel's operands stay MXU-shaped int8/uint8 tiles.

Works for any packed bits in {2, 3, 4, 8}: the unpacked values always fit
int8 (|q| <= 127), so W4A8 — the regime FPTQ shows is the practical
sweet spot — uses the exact same kernel as W8A8.

Template instance: MatmulSpec(epilogue="int8_mxu") from
`kernels/template.py`. Grid: (M/bm, N/bn, K/bk), K innermost,
accumulating across K steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.template import (MatmulSpec, matmul_grid, matmul_in_specs,
                                    matmul_out_spec, make_matmul_kernel,
                                    scale_operand)

_SPEC = MatmulSpec("w8a8_matmul", epilogue="int8_mxu")


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm", "bn",
                                             "bk", "interpret"))
def w8a8_matmul_pallas(xq: jax.Array, qw: jax.Array, scale: jax.Array, *,
                       bits: int, group_size: int, bm: int = 128,
                       bn: int = 128, bk: int = 256,
                       interpret: bool = False) -> jax.Array:
    """xq: (M, K) int8; qw: (packed_rows(K), N) uint8; scale: (G, N).
    Returns (M, N) f32 — *before* the per-token activation rescale."""
    m, k = xq.shape
    n = qw.shape[1]
    g = scale.shape[0]
    bm = min(bm, m)
    bk = min(bk, k)
    bn = min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    # every K-block must hold whole scale groups: the int32 accumulator is
    # rescaled group-by-group inside the block
    gs = group_size if group_size != -1 else k
    assert (gs >= bk and gs % bk == 0) or (gs < bk and bk % gs == 0)

    dims = dict(k=k, g=g, bm=bm, bn=bn, bk=bk)
    return pl.pallas_call(
        make_matmul_kernel(_SPEC, bits=bits, bk=bk),
        grid=matmul_grid(_SPEC, e=1, m=m, n=n, k=k, bm=bm, bn=bn, bk=bk),
        in_specs=matmul_in_specs(_SPEC, bits=bits, **dims),
        out_specs=matmul_out_spec(_SPEC, bm=bm, bn=bn),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="w8a8_matmul_pallas",
    )(xq, qw, scale_operand(scale, k=k, bk=bk))
