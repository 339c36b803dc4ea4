"""Pallas TPU kernel: expert-batched true W4A8/W8A8 int8 MXU matmul.

Closes the last fake-quant gap: a quantized MoE with `act_bits == 8` used
to fake-quantize activations to a bf16 grid and run the bf16 dequant
kernel — the weights were unpacked to float even though both operands were
already integer-grid. Now the expert capacity blocks are dynamically
quantized to int8 per token (like the dense A8 path) and each expert slab
runs the same int8 x int8 -> int32 MXU epilogue as the dense W8A8 kernel,
with the per-(expert, token) activation scale applied by the caller
(kernels/ops.py).

Template instance: MatmulSpec(expert_dim=True, epilogue="int8_mxu") — the
dense int8 epilogue from `kernels/template.py` lifted over a leading
expert grid axis. Grid: (E, C/bm, N/bn, K/bk), K innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.template import (MatmulSpec, matmul_grid, matmul_in_specs,
                                    matmul_out_spec, make_matmul_kernel,
                                    scale_operand)

_SPEC = MatmulSpec("expert_w8a8_matmul", epilogue="int8_mxu",
                   expert_dim=True)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm", "bn",
                                             "bk", "interpret"))
def expert_w8a8_matmul_pallas(xq: jax.Array, qw: jax.Array, scale: jax.Array,
                              *, bits: int, group_size: int, bm: int = 128,
                              bn: int = 128, bk: int = 256,
                              interpret: bool = False) -> jax.Array:
    """xq: (E, C, K) int8; qw: (E, packed_rows(K), N) uint8;
    scale: (E, G, N). Returns (E, C, N) f32 — *before* the per-token
    activation rescale."""
    e, c, k = xq.shape
    n = qw.shape[-1]
    g = scale.shape[-2]
    bm = min(bm, c)
    bk = min(bk, k)
    bn = min(bn, n)
    assert c % bm == 0 and k % bk == 0 and n % bn == 0, (c, k, n, bm, bk, bn)
    gs = group_size if group_size != -1 else k
    assert (gs >= bk and gs % bk == 0) or (gs < bk and bk % gs == 0)

    dims = dict(k=k, g=g, bm=bm, bn=bn, bk=bk)
    return pl.pallas_call(
        make_matmul_kernel(_SPEC, bits=bits, bk=bk),
        grid=matmul_grid(_SPEC, e=e, m=c, n=n, k=k, bm=bm, bn=bn, bk=bk),
        in_specs=matmul_in_specs(_SPEC, bits=bits, **dims),
        out_specs=matmul_out_spec(_SPEC, bm=bm, bn=bn),
        out_shape=jax.ShapeDtypeStruct((e, c, n), jnp.float32),
        interpret=interpret,
        name="expert_w8a8_matmul_pallas",
    )(xq, qw, scale_operand(scale, k=k, bk=bk))
