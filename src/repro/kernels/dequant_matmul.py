"""Pallas TPU kernel: fused low-bit dequantize + matmul.

The deployment hot-spot of weight-only PTQ (the paper's serving story):
y = x @ dequant(qw, scale). Packed uint8 weights stream HBM->VMEM at 1/2
(W4), 3/16 (W3) or 1/4 (W2) of bf16 bytes; sub-byte fields are unpacked
with lane-local shift/mask ops in VREGs, scaled per group, and fed to the
MXU as (bk, bn) bf16 tiles.

Since the kernel-template refactor this module is a spec instance: the
body, grid and block specs come from `kernels/template.py`
(MatmulSpec(epilogue="dequant_bf16")); only the `pl.pallas_call` site —
and with it the RL004 contract identity — lives here. See DESIGN.md
"Kernel templates & autotuning".

Grid: (M/bm, N/bn, K/bk), K innermost; the f32 output tile accumulates
across the K steps in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant.types import pack_layout
from repro.kernels.template import (MatmulSpec, matmul_grid, matmul_in_specs,
                                    matmul_out_spec, make_matmul_kernel,
                                    scale_operand)

_SPEC = MatmulSpec("dequant_matmul", epilogue="dequant_bf16")


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm", "bn",
                                             "bk", "interpret"))
def dequant_matmul_pallas(x: jax.Array, qw: jax.Array, scale: jax.Array, *,
                          bits: int, group_size: int, bm: int = 128,
                          bn: int = 128, bk: int = 256,
                          interpret: bool = False) -> jax.Array:
    """x: (M, K); qw: (packed_rows(K), N) uint8; scale: (G, N) -> (M, N) f32."""
    m, k = x.shape
    n = qw.shape[1]
    g = scale.shape[0]
    vpg = pack_layout(bits)[1]
    bm = min(bm, m)
    bk = min(bk, k)
    bn = min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    assert bk % vpg == 0

    dims = dict(k=k, g=g, bm=bm, bn=bn, bk=bk)
    return pl.pallas_call(
        make_matmul_kernel(_SPEC, bits=bits, bk=bk),
        grid=matmul_grid(_SPEC, e=1, m=m, n=n, k=k, bm=bm, bn=bn, bk=bk),
        in_specs=matmul_in_specs(_SPEC, bits=bits, **dims),
        out_specs=matmul_out_spec(_SPEC, bm=bm, bn=bn),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="dequant_matmul_pallas",
    )(x, qw, scale_operand(scale, k=k, bk=bk))
