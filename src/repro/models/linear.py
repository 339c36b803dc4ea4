"""Single entry point for every linear layer in the zoo.

A linear's params are {"w": W} or {"w": W, "b": b}. W may be a plain array
(K, N) / stacked experts (E, K, N), or a packed `QuantizedTensor` — the
paper's deployment format. Dispatch (see DESIGN.md "Quantized serving fast
paths" for the full table):

  * plain array                    -> jnp.einsum (MXU)
  * QuantizedTensor, TPU           -> Pallas fused dequant-matmul kernel
    - (K, N) weight                  -> kernels/dequant_matmul
    - (E, K, N) stacked experts      -> kernels/grouped_expert_matmul
      (rows in expert groups; only hit experts' packed slabs are read,
      never a float stack)
    - act_bits == 8                  -> kernels/w8a8_matmul (true int8 MXU)
    - act_bits == 8, stacked experts -> the grouped kernel's int8 MXU
      instance
  * QuantizedTensor, CPU           -> reference dequant + einsum (experts:
    `jax.lax.ragged_dot`) / the int32 W8A8 reference (same math)
  * float stacked experts          -> `jax.lax.ragged_dot`

`act_bits == 8` selects the true A8 path: per-token int8 activation
quantization feeding an int8 x int8 -> int32 matmul (FPTQ's W4A8/W8A8
regime). Other act_bits values keep the legacy per-tensor fake-quant.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.quant.types import (QuantizedTensor, dequantize,
                                    fake_quant_activation,
                                    quantize_activation)
from repro.debug_flags import dequant_impl

_KERNEL_BITS = (2, 3, 4, 8)


def _use_pallas() -> bool:
    force = dequant_impl()
    if force == "pallas":
        return True
    if force == "ref":
        return False
    return jax.default_backend() == "tpu"


def materialize(w: Any, dtype) -> jax.Array:
    if isinstance(w, QuantizedTensor):
        return dequantize(w, dtype)
    return w.astype(dtype)


def _dense_quantized(w: QuantizedTensor, x: jax.Array, dtype,
                     reduce_axis: str | None = None) -> jax.Array:
    """2-D quantized matmul: route to the W8A8 int8 path, the fused
    dequant kernel, or the reference dequant + einsum. `reduce_axis` (TP
    row-parallel: K split over that shard axis) makes the A8 per-token
    activation grid global via a pmax'ed amax — the psum of the partial
    outputs itself stays in `dense` below."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if w.act_bits == 8 and w.bits in _KERNEL_BITS:
        if _use_pallas():
            from repro.kernels import ops as kops

            y2 = kops.w8a8_matmul(x2, w, out_dtype=dtype,
                                  amax_axis=reduce_axis)
        else:
            from repro.kernels import ref as kref

            xq, xs = quantize_activation(x2, 8, axis_name=reduce_axis)
            y2 = (kref.w8a8_matmul_ref(xq, w.qw, w.scale, bits=w.bits,
                                       group_size=w.group_size,
                                       k=w.k) * xs).astype(dtype)
    else:
        if w.act_bits:  # legacy per-tensor fake-quant (act_bits != 8)
            x2 = fake_quant_activation(x2, w.act_bits,
                                       axis_name=reduce_axis)
        if _use_pallas() and w.bits in _KERNEL_BITS:
            from repro.kernels import ops as kops

            y2 = kops.dequant_matmul(x2, w, out_dtype=dtype)
        else:
            y2 = jnp.einsum("mk,kn->mn", x2, dequantize(w, dtype),
                            preferred_element_type=jnp.float32).astype(dtype)
    return y2.reshape(*lead, w.n)


def dense(p: dict, x: jax.Array, *, dtype=None,
          reduce_axis: str | None = None) -> jax.Array:
    """y = x @ w (+ b). x: (..., K). Handles quantized + biased linears.

    `reduce_axis` marks a *row-parallel* call under tensor parallelism: the
    weight's K dim is sharded over that mesh axis, so the per-shard matmul
    is a partial sum that is psum'ed before the bias is added (adding the
    replicated bias per-shard would count it `tp` times). Callers pass it
    only inside the serving shard_map (cfg.tp > 1)."""
    w = p["w"]
    dtype = dtype or x.dtype
    if isinstance(w, QuantizedTensor) and w.qw.ndim == 2:
        y = _dense_quantized(w, x, dtype, reduce_axis=reduce_axis)
    elif isinstance(w, QuantizedTensor):
        if w.act_bits:
            x = fake_quant_activation(x, w.act_bits)
        wm = dequantize(w, dtype)
        y = jnp.einsum("...k,kn->...n", x, wm,
                       preferred_element_type=jnp.float32).astype(dtype)
    else:
        y = jnp.einsum("...k,kn->...n", x.astype(dtype), w.astype(dtype),
                       preferred_element_type=jnp.float32).astype(dtype)
    if reduce_axis is not None:
        y = jax.lax.psum(y, reduce_axis)
    if "b" in p and p["b"] is not None:
        y = y + p["b"].astype(dtype)
    return y


class ExpertGroups(NamedTuple):
    """Rows in expert groups (models/mlp_moe.py builds it): expert e's
    rows are `sizes[e]` consecutive rows, a whole number of `bm`-row
    tiles, experts in order; `tile_expert` (R // bm,) names each tile's
    expert and `n_live` (1,) counts the tiles that hold rows."""

    sizes: jax.Array
    tile_expert: jax.Array
    n_live: jax.Array
    bm: int


def grouped_experts(p: dict, x: jax.Array, groups: ExpertGroups, *,
                    dtype=None) -> jax.Array:
    """Grouped expert matmul: x (R, K) rows in `groups` @ w (E, K, N) ->
    (R, N), each row through its own expert; rows past the groups are
    zero. Quantized stacks on the chip run the grouped Pallas kernels,
    which read only the packed slabs of experts that hold rows."""
    w = p["w"]
    dtype = dtype or x.dtype
    if isinstance(w, QuantizedTensor):
        kernel = _use_pallas() and w.bits in _KERNEL_BITS
        if w.act_bits == 8 and w.bits in _KERNEL_BITS:
            # true W4A8/W8A8 expert path: per-token int8 rows feed the
            # int8 x int8 -> int32 MXU dots (no bf16 dequant stack)
            if kernel:
                from repro.kernels import ops as kops

                return kops.grouped_expert_w8a8_matmul(
                    x, w, groups.tile_expert, groups.n_live, bm=groups.bm,
                    out_dtype=dtype)
            from repro.kernels import ref as kref

            xq, xs = quantize_activation(x, 8)
            y = kref.grouped_expert_w8a8_ref(
                xq, w.qw, w.scale, groups.tile_expert, groups.n_live,
                bits=w.bits, group_size=w.group_size, k=w.k, bm=groups.bm)
            return (y * xs).astype(dtype)
        if w.act_bits:
            x = fake_quant_activation(x, w.act_bits)
        if kernel:
            from repro.kernels import ops as kops

            return kops.grouped_expert_matmul(
                x, w, groups.tile_expert, groups.n_live, bm=groups.bm,
                out_dtype=dtype)
        w = dequantize(w, dtype)
    return jax.lax.ragged_dot(
        x.astype(dtype), w.astype(dtype), groups.sizes,
        preferred_element_type=jnp.float32).astype(dtype)


def init_dense(key, k: int, n: int, *, bias: bool = False, dtype=jnp.float32,
               scale: float | None = None) -> dict:
    std = scale if scale is not None else (1.0 / (k ** 0.5))
    p = {"w": (jax.random.normal(key, (k, n)) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((n,), dtype)
    return p


def init_dense_experts(key, e: int, k: int, n: int, *, dtype=jnp.float32,
                       scale: float | None = None) -> dict:
    std = scale if scale is not None else (1.0 / (k ** 0.5))
    return {"w": (jax.random.normal(key, (e, k, n)) * std).astype(dtype)}
