"""Pallas TPU kernel: fused paged attention with inline int8-KV dequant.

The serving read hot path (vLLM/PagedAttention-style). Instead of gathering
every slot's pages into a contiguous ``(S, maxp*page_size, ...)`` HBM view
and running a dense einsum (the PR-1 path, which reads — and for int8 KV
materializes in bf16 — the *provisioned* window regardless of fill), the
kernel walks the block table directly: per slot grid cell it streams one
page tile (all kv heads) per grid step HBM->VMEM, dequantizes int8 K/V
inline from the scale pools (which ride the same block table), and folds
the tile into per-head online-softmax accumulators held in VMEM scratch. Pages beyond a
slot's fill count — and, under sliding-window attention, pages wholly
behind the window — are never touched: their grid steps are routed to the
scratch page by the index map and skipped by ``pl.when``, so HBM traffic
scales with *live* tokens, not ``maxp*page_size`` padding.

Template instance: the page-walk body, liveness predicate, index maps and
``PrefetchScalarGridSpec`` all come from `kernels/template.py`
(:class:`PagedSpec`); only the ``pl.pallas_call`` site lives here. The
grid is ``(S, W * tiles_per_page)``, the page-walk axis innermost so the
per-head (m, l, acc) scratch accumulators carry across one slot's pages.

Multi-row regime (``m_rows > 1``) serves two callers through one body:
  * spec-decode *verify* — the draft's last ``m_rows`` tokens of a slot
    verified in one read;
  * chunked/suffix *prefill* — a slot's left-padded prefill chunk read
    against its own earlier pages plus any shared prefix pages, replacing
    the gather-oracle prefill path (row j of the padded chunk sits at fill
    position ``kv_len - m_rows + j`` exactly like a verify row, so ragged
    chunk lengths inside one padded bucket need no extra masking — pad
    rows carry positions < 0, write to the scratch page, and read as
    garbage the engine discards).
The query block is ``m_rows * G`` rows, laid out m-major, and the
causal/window masks become per-row fill limits. ``m_rows == 1`` reduces
exactly to the decode read — same masks, same accumulator updates,
bit-identical output.

Numerics mirror ``kernels/ref.paged_attention_ref`` op-for-op (same walk
order, same f32 accumulation) so interpret-mode runs are bit-comparable
with the jnp reference on CPU.

Tensor parallelism (serve/engine.py shard_map): the kernel runs per shard
on the local kv-head slice of the pools — the block's KVH dim shrinks to
KVH/tp while the scalar-prefetched block table / fill counts stay
replicated, and per-head online softmax needs no cross-shard collective
(the psum lives at the attention output projection, outside the kernel).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.template import (NEG, PagedSpec, make_paged_kernel,
                                    paged_grid_spec)

__all__ = ["paged_attention_pallas", "NEG"]


@functools.partial(jax.jit, static_argnames=("window", "tile", "m_rows",
                                             "interpret"))
def paged_attention_pallas(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           block_table: jax.Array, kv_len: jax.Array,
                           k_scale_pool: Optional[jax.Array] = None,
                           v_scale_pool: Optional[jax.Array] = None, *,
                           window: Optional[int] = None, tile: int = 0,
                           m_rows: int = 1,
                           interpret: bool = False) -> jax.Array:
    """q: (S, KVH, m_rows*G, hd) m-major rows; pools: (P, page, KVH,
    hd[/hd_v]); block_table: (S, W) page ids (-1 = unheld); kv_len: (S,)
    fill counts *including* all m_rows query tokens (row m sits at
    position kv_len - m_rows + m; at m_rows == 1 q is the current token at
    kv_len - 1). Scale pools (P, page, KVH) mark int8 pools. Returns
    (S, KVH, m_rows*G, hd_v) f32."""
    s, kvh, rows, hd = q.shape
    assert rows % m_rows == 0, (rows, m_rows)
    page_size = k_pool.shape[1]
    hd_v = v_pool.shape[-1]
    w = block_table.shape[1]
    tile = tile or page_size
    quant = k_scale_pool is not None
    n_steps = w * (page_size // tile)
    sm_scale = 1.0 / (hd ** 0.5)
    spec = PagedSpec(page_size=page_size, tile=tile, window=window,
                     m_rows=m_rows, quant=quant)

    args = [q, k_pool, v_pool]
    if quant:
        args += [k_scale_pool.astype(jnp.float32),
                 v_scale_pool.astype(jnp.float32)]
    return pl.pallas_call(
        make_paged_kernel(spec, sm_scale=sm_scale, n_steps=n_steps),
        grid_spec=paged_grid_spec(spec, s=s, kvh=kvh, rows=rows, hd=hd,
                                  hd_v=hd_v, n_steps=n_steps),
        out_shape=jax.ShapeDtypeStruct((s, kvh, rows, hd_v), jnp.float32),
        interpret=interpret,
        name="paged_attention_pallas",
    )(block_table.astype(jnp.int32), kv_len.astype(jnp.int32), *args)
