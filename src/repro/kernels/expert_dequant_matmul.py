"""Pallas TPU kernel: expert-batched fused low-bit dequantize + matmul.

The MoE serving hot-spot: every expert's packed weight slab is consumed
directly from the stacked (E, packed_rows(K), N) layout, so a quantized
Mixtral/DeepSeek/Jamba MoE block never materializes a float (E, K, N)
expert stack in HBM (the former `dequantize`-then-einsum path did exactly
that, and at W4 the float stack is 4x the packed bytes).

Template instance: MatmulSpec(expert_dim=True, epilogue="dequant_bf16") —
the dense dequant body and block specs from `kernels/template.py`, lifted
over a leading expert grid axis. Grid: (E, M/bm, N/bn, K/bk), K innermost;
each (e, i, j) output tile accumulates across K steps in VMEM, and the
expert dimension is the outermost loop so one expert's packed tiles stream
HBM->VMEM while the previous expert's tail is still in flight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.template import (MatmulSpec, matmul_grid, matmul_in_specs,
                                    matmul_out_spec, make_matmul_kernel,
                                    scale_operand)

_SPEC = MatmulSpec("expert_dequant_matmul", epilogue="dequant_bf16",
                   expert_dim=True)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm", "bn",
                                             "bk", "interpret"))
def expert_dequant_matmul_pallas(x: jax.Array, qw: jax.Array,
                                 scale: jax.Array, *, bits: int,
                                 group_size: int, bm: int = 128,
                                 bn: int = 128, bk: int = 256,
                                 interpret: bool = False) -> jax.Array:
    """x: (E, M, K); qw: (E, packed_rows(K), N) uint8; scale: (E, G, N).
    Returns (E, M, N) f32."""
    e, m, k = x.shape
    n = qw.shape[-1]
    g = scale.shape[-2]
    bm = min(bm, m)
    bk = min(bk, k)
    bn = min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)

    dims = dict(k=k, g=g, bm=bm, bn=bn, bk=bk)
    return pl.pallas_call(
        make_matmul_kernel(_SPEC, bits=bits, bk=bk),
        grid=matmul_grid(_SPEC, e=e, m=m, n=n, k=k, bm=bm, bn=bn, bk=bk),
        in_specs=matmul_in_specs(_SPEC, bits=bits, **dims),
        out_specs=matmul_out_spec(_SPEC, bm=bm, bn=bn),
        out_shape=jax.ShapeDtypeStruct((e, m, n), jnp.float32),
        interpret=interpret,
        name="expert_dequant_matmul_pallas",
    )(x, qw, scale_operand(scale, k=k, bk=bk))
