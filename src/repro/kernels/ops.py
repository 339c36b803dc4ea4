"""Jitted public wrappers around the Pallas kernels.

Dispatch: real TPU -> compiled Pallas; CPU -> `interpret=True` when forced
via REPRO_DEQUANT_IMPL=pallas (tests), else the jnp reference (same math,
fast on CPU). Handles token-dim padding and block-size selection so callers
never deal with tiling constraints.

Block-size selection consults `kernels/autotune.py` per shape class: a
measured JSON config cache when warm, else the deterministic fallback
table (the former hand heuristics — see DESIGN.md "Kernel templates &
autotuning"). The table has two regimes: prefill-shaped calls (M > 8) use
square-ish tiles, while decode-shaped skinny-M calls (M <= 8 — one token
per serving slot) keep bm at the minimal 8-row tile and widen bn/bk
instead, so per-step decode streams more packed weight bytes per grid
step instead of padding tokens up to prefill tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant.types import (QuantizedTensor, dequantize, pack_layout,
                                    quantize_activation)
from repro.debug_flags import dequant_impl
from repro.kernels import autotune, ref
from repro.kernels.channel_stats import channel_stats_pallas
from repro.kernels.dequant_matmul import dequant_matmul_pallas
from repro.kernels.expert_dequant_matmul import expert_dequant_matmul_pallas
from repro.kernels.expert_w8a8_matmul import expert_w8a8_matmul_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.quantize import quantize_pack_pallas
from repro.kernels.w8a8_matmul import w8a8_matmul_pallas

# Kernel-contract registry: every `pl.pallas_call` site in the tree maps
# to exactly one entry here, keyed by the wrapper function that contains
# it, declaring the jnp reference oracle it is differentially tested
# against and the parity test(s) that do the comparison. repro-lint RL004
# cross-checks all three directions (site without entry, stale entry,
# oracle/parity id that doesn't resolve), so an unregistered — i.e.
# unverified — kernel cannot land. Kept a *pure literal* so the linter can
# ast.literal_eval it without importing (and tracing) kernel code.
KERNEL_CONTRACTS = {
    "dequant_matmul_pallas": {
        "module": "repro.kernels.dequant_matmul",
        "ref": "repro.kernels.ref:dequant_matmul_ref",
        "parity": ("tests/test_kernel_parity.py::test_dense_parity",),
    },
    "expert_dequant_matmul_pallas": {
        "module": "repro.kernels.expert_dequant_matmul",
        "ref": "repro.kernels.ref:expert_dequant_matmul_ref",
        "parity": ("tests/test_kernel_parity.py::test_expert_parity",),
    },
    "w8a8_matmul_pallas": {
        "module": "repro.kernels.w8a8_matmul",
        "ref": "repro.kernels.ref:w8a8_matmul_ref",
        "parity": ("tests/test_kernel_parity.py::test_w8a8_parity",),
    },
    "expert_w8a8_matmul_pallas": {
        "module": "repro.kernels.expert_w8a8_matmul",
        "ref": "repro.kernels.ref:expert_w8a8_matmul_ref",
        "parity": ("tests/test_kernel_parity.py::test_expert_w8a8_parity",),
    },
    "quantize_pack_pallas": {
        "module": "repro.kernels.quantize",
        "ref": "repro.kernels.ref:quantize_pack_ref",
        "parity": ("tests/test_kernels.py::test_quantize_pack_vs_ref",),
    },
    "channel_stats_pallas": {
        "module": "repro.kernels.channel_stats",
        "ref": "repro.kernels.ref:channel_stats_ref",
        "parity": ("tests/test_kernels.py::test_channel_stats_vs_ref",),
    },
    "paged_attention_pallas": {
        "module": "repro.kernels.paged_attention",
        "ref": "repro.kernels.ref:paged_attention_ref",
        "parity": (
            "tests/test_kernel_parity.py::test_paged_attention_parity",
            "tests/test_kernel_parity.py::test_paged_attention_verify_parity",
            "tests/test_kernel_parity.py::test_paged_attention_prefill_parity",
        ),
    },
}

# tile heuristics live in kernels/autotune.py now (they are its
# deterministic fallback table); the aliases keep this module the single
# import point the dispatch-regime unit tests pin against
_pick_block = autotune.pick_block
_pick_bk = autotune.pick_bk
_matmul_blocks = autotune.matmul_blocks
_paged_tile = autotune.fallback_paged_tile


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _plan_tiles(m: int, k: int, n: int, qt: QuantizedTensor,
                bm: int, bn: int, bk: int, *, kind: str):
    """Shared dispatch planning for every quantized-matmul wrapper:
    autotuned plan for the shape class when the config cache is warm, else
    the deterministic table. Returns None when K admits no valid block —
    callers fall back to the jnp ref."""
    return autotune.matmul_plan(kind, m, k, n, bits=qt.bits,
                                group_size=qt.group_size, bm=bm, bn=bn,
                                bk=bk)


def dequant_matmul(x: jax.Array, qt: QuantizedTensor, *, out_dtype=None,
                   bm: int = 128, bn: int = 256, bk: int = 256) -> jax.Array:
    """x: (M, K) @ packed (K, N) -> (M, N). Pads M to the tile size.

    Differentiable in x — norm tweaking takes the gradient of its loss
    through the packed layer. Pallas kernels define no VJP, so the
    backward multiplies the cotangent by the dequantized weight."""
    return _dequant_matmul_vjp(
        x, qt.qw, qt.scale, (qt.bits, qt.group_size, tuple(qt.shape)),
        jnp.dtype(out_dtype or x.dtype), (bm, bn, bk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _dequant_matmul_vjp(x, qw, scale, meta, out_dtype, tiles):
    bits, group_size, _ = meta
    bm, bn, bk = tiles
    m, k = x.shape
    plan = autotune.matmul_plan("dequant", m, k, qw.shape[-1], bits=bits,
                                group_size=group_size, bm=bm, bn=bn, bk=bk)
    if plan is None:
        y = ref.dequant_matmul_ref(x, qw, scale, bits=bits,
                                   group_size=group_size, k=k)
        return y.astype(out_dtype)
    bm_, bn_, bk_ = plan
    pad_m = (-m) % bm_
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    y = dequant_matmul_pallas(x, qw, scale, bits=bits,
                              group_size=group_size, bm=bm_, bn=bn_,
                              bk=bk_, interpret=_interpret())
    if pad_m:
        y = y[:m]
    return y.astype(out_dtype)


def _dequant_matmul_fwd(x, qw, scale, meta, out_dtype, tiles):
    return (_dequant_matmul_vjp(x, qw, scale, meta, out_dtype, tiles),
            (x, qw, scale))


def _dequant_matmul_bwd(meta, out_dtype, tiles, res, g):
    x, qw, scale = res
    bits, group_size, shape = meta
    w = dequantize(QuantizedTensor(qw, scale, bits, group_size, shape),
                   jnp.float32)
    gx = jnp.dot(g.astype(jnp.float32), w.T,
                 preferred_element_type=jnp.float32)
    return (gx.astype(x.dtype), np.zeros(qw.shape, jax.dtypes.float0),
            jnp.zeros_like(scale))


_dequant_matmul_vjp.defvjp(_dequant_matmul_fwd, _dequant_matmul_bwd)


def expert_dequant_matmul(x: jax.Array, qt: QuantizedTensor, *,
                          out_dtype=None, bm: int = 128, bn: int = 256,
                          bk: int = 256) -> jax.Array:
    """Expert-batched x: (E, C, K) @ packed (E, K, N) -> (E, C, N).

    Consumes the stacked packed layout directly — no float (E, K, N)
    expert stack is ever materialized. Pads the capacity dim to the tile
    size; decode-shaped capacities (C <= 8) take the skinny tiles."""
    out_dtype = out_dtype or x.dtype
    e, c, k = x.shape
    plan = _plan_tiles(c, k, qt.n, qt, bm, bn, bk, kind="expert_dequant")
    if plan is None:
        y = ref.expert_dequant_matmul_ref(x, qt.qw, qt.scale, bits=qt.bits,
                                          group_size=qt.group_size, k=k)
        return y.astype(out_dtype)
    bm_, bn_, bk_ = plan
    pad_c = (-c) % bm_
    if pad_c:
        x = jnp.pad(x, ((0, 0), (0, pad_c), (0, 0)))
    y = expert_dequant_matmul_pallas(x, qt.qw, qt.scale, bits=qt.bits,
                                     group_size=qt.group_size, bm=bm_,
                                     bn=bn_, bk=bk_, interpret=_interpret())
    if pad_c:
        y = y[:, :c]
    return y.astype(out_dtype)


def w8a8_matmul(x: jax.Array, qt: QuantizedTensor, *, out_dtype=None,
                bm: int = 128, bn: int = 256, bk: int = 256,
                amax_axis: str | None = None) -> jax.Array:
    """True A8 path: per-token int8 activation quantize, int8 x int8 -> int32
    MXU matmul, per-(token, channel-group) rescale. x: (M, K) -> (M, N).
    `amax_axis`: shard axis the K dim is split over (TP row-parallel) — the
    activation amax is pmax'ed so every shard uses the single-device grid."""
    out_dtype = out_dtype or x.dtype
    m, k = x.shape
    xq, xs = quantize_activation(x, 8, axis_name=amax_axis)  # int8, (M,1) f32
    plan = _plan_tiles(m, k, qt.n, qt, bm, bn, bk, kind="w8a8")
    if plan is None:
        y = ref.w8a8_matmul_ref(xq, qt.qw, qt.scale, bits=qt.bits,
                                group_size=qt.group_size, k=k)
        return (y * xs).astype(out_dtype)
    bm_, bn_, bk_ = plan
    pad_m = (-m) % bm_
    if pad_m:
        xq = jnp.pad(xq, ((0, pad_m), (0, 0)))
    y = w8a8_matmul_pallas(xq, qt.qw, qt.scale, bits=qt.bits,
                           group_size=qt.group_size, bm=bm_, bn=bn_,
                           bk=bk_, interpret=_interpret())
    if pad_m:
        y = y[:m]
    return (y * xs).astype(out_dtype)


def expert_w8a8_matmul(x: jax.Array, qt: QuantizedTensor, *, out_dtype=None,
                       bm: int = 128, bn: int = 256, bk: int = 256,
                       amax_axis: str | None = None) -> jax.Array:
    """Expert-batched true A8 path: per-token int8 activation quantize over
    the flattened (E*C) token dim, int8 x int8 -> int32 MXU matmul per
    expert slab, per-(expert, token) rescale. x: (E, C, K) -> (E, C, N).
    Replaces the fake-quant + bf16-dequant detour the MoE act_bits=8 path
    used to take."""
    out_dtype = out_dtype or x.dtype
    e, c, k = x.shape
    xq, xs = quantize_activation(x.reshape(e * c, k), 8, axis_name=amax_axis)
    xq = xq.reshape(e, c, k)
    xs = xs.reshape(e, c, 1)
    plan = _plan_tiles(c, k, qt.n, qt, bm, bn, bk, kind="expert_w8a8")
    if plan is None:
        y = ref.expert_w8a8_matmul_ref(xq, qt.qw, qt.scale, bits=qt.bits,
                                       group_size=qt.group_size, k=k)
        return (y * xs).astype(out_dtype)
    bm_, bn_, bk_ = plan
    pad_c = (-c) % bm_
    if pad_c:
        xq = jnp.pad(xq, ((0, 0), (0, pad_c), (0, 0)))
    y = expert_w8a8_matmul_pallas(xq, qt.qw, qt.scale, bits=qt.bits,
                                  group_size=qt.group_size, bm=bm_, bn=bn_,
                                  bk=bk_, interpret=_interpret())
    if pad_c:
        y = y[:, :c]
    return (y * xs).astype(out_dtype)


def _kv_dtype(k_scale_pool) -> str:
    return "int8" if k_scale_pool is not None else "bf16"


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_table: jax.Array, kv_len: jax.Array, *,
                    k_scale_pool=None, v_scale_pool=None, window=None,
                    out_dtype=None) -> jax.Array:
    """Fused paged-attention decode: q (S, H, hd) one token per slot against
    the slot's block-table pages, int8 K/V dequantized inline from the scale
    pools. Returns (S, H, hd_v) without materializing the gathered
    (S, maxp*page_size, ...) KV view. CPU default runs the jnp page-walk
    reference (same math); REPRO_DEQUANT_IMPL=pallas lowers the kernel in
    interpret mode; TPU compiles it.

    Under tensor-parallel serving this op is invoked *per shard* inside the
    engine's shard_map: the pools arrive with the shard-local kv-head slice
    (KVH/tp) while block tables and fill counts are replicated scalars
    (scalar-prefetch inputs are never sharded), so the kernel's head loop
    simply shrinks to KVH/tp — attention is head-independent and the kernel needs
    no TP awareness. H here is the shard-local head count; the GQA group
    width H/KVH is TP-invariant because legal widths divide n_kv_heads."""
    s, h, hd = q.shape
    kvh = k_pool.shape[2]
    qg = q.reshape(s, kvh, h // kvh, hd)
    tile = autotune.paged_tile(k_pool.shape[1], _kv_dtype(k_scale_pool), 1)
    if _interpret() and dequant_impl() != "pallas":
        o = ref.paged_attention_ref(qg, k_pool, v_pool, block_table, kv_len,
                                    k_scale_pool, v_scale_pool,
                                    window=window, tile=tile)
    else:
        o = paged_attention_pallas(qg, k_pool, v_pool, block_table, kv_len,
                                   k_scale_pool, v_scale_pool, window=window,
                                   tile=tile, interpret=_interpret())
    return o.reshape(s, h, v_pool.shape[-1]).astype(out_dtype or q.dtype)


def _paged_rows_read(ref_fn, q: jax.Array, k_pool: jax.Array,
                     v_pool: jax.Array, block_table: jax.Array,
                     kv_len: jax.Array, *, k_scale_pool=None,
                     v_scale_pool=None, window=None,
                     out_dtype=None) -> jax.Array:
    """Shared multi-row page walk behind the verify and prefill reads:
    q (S, M, H, hd) — M tail tokens per slot, row m at fill position
    kv_len - M + m — against the slot's pages, with per-row causal fill
    masks. kv_len counts the fill *including* all M tokens. Returns
    (S, M, H, hd_v). One page walk serves all M rows, so each live KV tile
    streams once instead of M times."""
    s, m, h, hd = q.shape
    kvh = k_pool.shape[2]
    g = h // kvh
    # rows go m-major within each kv head: (S, KVH, M*G, hd)
    qg = q.reshape(s, m, kvh, g, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(s, kvh, m * g, hd)
    tile = autotune.paged_tile(k_pool.shape[1], _kv_dtype(k_scale_pool), m)
    if _interpret() and dequant_impl() != "pallas":
        o = ref_fn(qg, k_pool, v_pool, block_table, kv_len,
                   k_scale_pool, v_scale_pool, window=window, tile=tile,
                   m_rows=m)
    else:
        o = paged_attention_pallas(qg, k_pool, v_pool, block_table, kv_len,
                                   k_scale_pool, v_scale_pool, window=window,
                                   tile=tile, m_rows=m,
                                   interpret=_interpret())
    hd_v = v_pool.shape[-1]
    o = o.reshape(s, kvh, m, g, hd_v).transpose(0, 2, 1, 3, 4)
    return o.reshape(s, m, h, hd_v).astype(out_dtype or q.dtype)


def paged_attention_verify(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           kv_len: jax.Array, *, k_scale_pool=None,
                           v_scale_pool=None, window=None,
                           out_dtype=None) -> jax.Array:
    """Fused verify read for self-speculative decoding: the M rows are the
    draft-proposed tail tokens of each slot (see `_paged_rows_read`).
    M == 1 is exactly the decode read (`paged_attention`)."""
    return _paged_rows_read(
        ref.paged_attention_ref, q, k_pool, v_pool, block_table, kv_len,
        k_scale_pool=k_scale_pool, v_scale_pool=v_scale_pool, window=window,
        out_dtype=out_dtype)


def paged_attention_prefill(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_table: jax.Array,
                            kv_len: jax.Array, *, k_scale_pool=None,
                            v_scale_pool=None, window=None,
                            out_dtype=None) -> jax.Array:
    """Fused chunked/suffix-prefill read: the M rows are a slot's
    left-padded prefill chunk (row j holds the token at fill position
    kv_len - M + j whatever the row's real chunk length — left-padding
    makes ragged chunks line up on the same per-row fill limits the verify
    read uses; pad rows carry positions < 0, land on the scratch page, and
    read back as values the engine discards). Replaces the
    gather-the-context oracle on the prefill hot path: earlier context —
    the slot's own prior chunks or shared prefix pages — streams through
    the same page walk as decode instead of materializing a contiguous
    (S, width*page_size, ...) HBM view."""
    return _paged_rows_read(
        ref.paged_attention_prefill_ref, q, k_pool, v_pool, block_table,
        kv_len, k_scale_pool=k_scale_pool, v_scale_pool=v_scale_pool,
        window=window, out_dtype=out_dtype)


def channel_stats(x: jax.Array):
    """x: (..., C) -> per-channel (mean, var)."""
    x2 = x.reshape(-1, x.shape[-1])
    t, c = x2.shape
    if _interpret() and dequant_impl() != "pallas":
        return ref.channel_stats_ref(x2)
    bt = _pick_block(t, 256)
    bc = _pick_block(c, 256)
    return channel_stats_pallas(x2, bt=bt, bc=bc, interpret=_interpret())


def quantize_pack(w: jax.Array, scale: jax.Array, *, bits: int,
                  group_size: int) -> jax.Array:
    k, n = w.shape
    if _interpret() and dequant_impl() != "pallas":
        return ref.quantize_pack_ref(w, scale, bits=bits)
    gs = group_size if group_size != -1 else k
    vpg = pack_layout(bits)[1]
    bk = _pick_bk(k, gs, vpg, 256)
    if bk is None:  # no valid tiling (e.g. group_size with odd factors)
        return ref.quantize_pack_ref(w, scale, bits=bits)
    bn = _pick_block(n, 256)
    return quantize_pack_pallas(w, scale, bits=bits, group_size=group_size,
                                bk=bk, bn=bn, interpret=_interpret())
