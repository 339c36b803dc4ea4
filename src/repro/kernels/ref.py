"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant.types import (QuantizedTensor, compute_scales,
                                    dequantize, pack, quantize_values, unpack)


def dequant_matmul_ref(x: jax.Array, qw: jax.Array, scale: jax.Array, *,
                       bits: int, group_size: int, k: int) -> jax.Array:
    qt = QuantizedTensor(qw, scale, bits, group_size, (k, qw.shape[1]))
    w = dequantize(qt, jnp.float32)
    return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def w8a8_matmul_ref(xq: jax.Array, qw: jax.Array, scale: jax.Array, *,
                    bits: int, group_size: int, k: int) -> jax.Array:
    """Exact int32 oracle for the W8A8 kernel (pre activation-rescale).
    xq: (M, K) int8; qw: (pk, N) packed rows; scale: (G, N). Returns
    (M, N) f32."""
    m = xq.shape[0]
    n = qw.shape[1]
    q = unpack(qw, bits, k)                            # (K, N) int32
    g = scale.shape[0]
    acc = jnp.einsum("mgk,gkn->mgn",
                     xq.astype(jnp.int32).reshape(m, g, k // g),
                     q.reshape(g, k // g, n),
                     preferred_element_type=jnp.int32)
    return jnp.sum(acc.astype(jnp.float32) *
                   scale.astype(jnp.float32)[None], axis=1)


def _tiles(x: jax.Array, bm: int) -> jax.Array:
    return x.reshape(x.shape[0] // bm, bm, x.shape[-1])


def _live_rows(y: jax.Array, n_live: jax.Array) -> jax.Array:
    """(T, bm, N) tile results -> (T*bm, N), zero from tile n_live[0] on."""
    live = jnp.arange(y.shape[0]) < n_live[0]
    return jnp.where(live[:, None, None], y, 0.0).reshape(-1, y.shape[-1])


def grouped_expert_dequant_ref(x: jax.Array, qw: jax.Array,
                               scale: jax.Array, tile_expert: jax.Array,
                               n_live: jax.Array, *, bits: int,
                               group_size: int, k: int,
                               bm: int) -> jax.Array:
    """x: (R, K) rows in bm-row tiles, tile t times the packed (E, pk, N)
    slab of expert tile_expert[t], bf16 operands, f32 accumulation ->
    (R, N) f32, zero on tiles from n_live[0] on."""
    e = qw.shape[0]
    w = dequantize(QuantizedTensor(qw, scale, bits, group_size,
                                   (e, k, qw.shape[-1])), jnp.float32)
    y = jnp.einsum("tmk,tkn->tmn", _tiles(x, bm).astype(jnp.bfloat16),
                   w[tile_expert].astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return _live_rows(y, n_live)


def grouped_expert_w8a8_ref(xq: jax.Array, qw: jax.Array, scale: jax.Array,
                            tile_expert: jax.Array, n_live: jax.Array, *,
                            bits: int, group_size: int, k: int,
                            bm: int) -> jax.Array:
    """Grouped W8A8 oracle: one exact `w8a8_matmul_ref` per row tile with
    its expert's slab. xq: (R, K) int8. Returns (R, N) f32 (pre
    activation-rescale), zero on dead tiles."""
    y = jax.vmap(lambda x2, w2, s2: w8a8_matmul_ref(
        x2, w2, s2, bits=bits, group_size=group_size, k=k))(
            _tiles(xq, bm), qw[tile_expert], scale[tile_expert])
    return _live_rows(y, n_live)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_table: jax.Array, kv_len: jax.Array,
                        k_scale_pool: Optional[jax.Array] = None,
                        v_scale_pool: Optional[jax.Array] = None, *,
                        window: Optional[int] = None,
                        tile: int = 0, m_rows: int = 1) -> jax.Array:
    """jnp mirror of kernels/paged_attention.py — same page-walk order, same
    per-tile online-softmax updates, same f32 accumulation, so interpret-mode
    kernel runs are bit-comparable on CPU. Dead tiles (beyond fill, unheld
    pages, wholly behind the sliding window) leave the accumulators
    untouched, exactly like the kernel's ``pl.when`` skip.

    q: (S, KVH, m_rows*G, hd) m-major rows (verify regime: row r belongs to
    the token at fill position kv_len - m_rows + r//G; decode is
    m_rows == 1); pools: (P, page, KVH, hd[/hd_v]); block_table: (S, W);
    kv_len: (S,). Returns (S, KVH, m_rows*G, hd_v) f32."""
    s, kvh, rows, hd = q.shape
    assert rows % m_rows == 0, (rows, m_rows)
    g = rows // m_rows
    page_size = k_pool.shape[1]
    hd_v = v_pool.shape[-1]
    w = block_table.shape[1]
    tile = tile or page_size
    assert page_size % tile == 0, (page_size, tile)
    nt = page_size // tile
    n_steps = w * nt
    quant = k_scale_pool is not None
    sm_scale = 1.0 / (hd ** 0.5)
    neg = -1e30

    def cell(qgh, bt_row, kl, h_idx):
        """One (slot, kv-head) grid cell: walk the row's page tiles."""
        qf = qgh.astype(jnp.float32)                         # (R, hd)

        def step(carry, t):
            m, l, acc = carry
            wi, sub, base = t // nt, t % nt, (t // nt) * page_size + \
                (t % nt) * tile
            live = (base < kl) & (bt_row[wi] >= 0)
            if window is not None:
                live &= (base + tile) > (kl - (m_rows - 1) - window)
            page = jnp.where(live, jnp.maximum(bt_row[wi], 0), 0)
            k = jax.lax.dynamic_slice(
                k_pool, (page, sub * tile, h_idx, 0),
                (1, tile, 1, hd))[0, :, 0, :]                # (tile, hd)
            v = jax.lax.dynamic_slice(
                v_pool, (page, sub * tile, h_idx, 0),
                (1, tile, 1, hd_v))[0, :, 0, :]              # (tile, hd_v)
            if quant:
                ks = jax.lax.dynamic_slice(
                    k_scale_pool, (page, sub * tile, h_idx),
                    (1, tile, 1))[0, :, 0].astype(jnp.float32)
                vs = jax.lax.dynamic_slice(
                    v_scale_pool, (page, sub * tile, h_idx),
                    (1, tile, 1))[0, :, 0].astype(jnp.float32)
                kf = k.astype(jnp.float32) * ks[:, None]
                vf = v.astype(jnp.float32) * vs[:, None]
            else:
                kf = k.astype(jnp.float32)
                vf = v.astype(jnp.float32)
            sc = jax.lax.dot_general(qf, kf, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            sc = sc * sm_scale                               # (R, tile)
            pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            # per-row causal fill limit (scalar kl at m_rows == 1)
            r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            lim = kl - (m_rows - 1 - r // g)
            valid = pos < lim
            if window is not None:
                valid &= pos > (lim - 1 - window)
            sc = jnp.where(valid, sc, neg)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            p = jnp.where(valid, p, 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * corr + jnp.dot(p, vf,
                                           preferred_element_type=jnp.float32)
            keep = lambda new, old: jnp.where(live, new, old)
            return (keep(m_new, m), keep(l_new, l), keep(acc_new, acc)), None

        init = (jnp.full((rows, 1), neg, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, hd_v), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(step, init,
                                      jnp.arange(n_steps, dtype=jnp.int32))
        return acc / jnp.maximum(l, 1e-30)

    heads = jnp.arange(kvh, dtype=jnp.int32)
    per_slot = jax.vmap(cell, in_axes=(0, None, None, 0))    # over kv-heads
    return jax.vmap(per_slot, in_axes=(0, 0, 0, None))(
        q, block_table.astype(jnp.int32), kv_len.astype(jnp.int32), heads)


def paged_attention_prefill_ref(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, block_table: jax.Array,
                                kv_len: jax.Array,
                                k_scale_pool: Optional[jax.Array] = None,
                                v_scale_pool: Optional[jax.Array] = None, *,
                                window: Optional[int] = None,
                                tile: int = 0, m_rows: int = 1) -> jax.Array:
    """Named oracle for the fused chunked/suffix-prefill read. The walk is
    identical to the verify regime of :func:`paged_attention_ref` — a
    prefill chunk's left-padded row j sits at fill position
    ``kv_len - m_rows + j`` exactly like a verify row — so this simply
    delegates; the separate name keeps the KERNEL_CONTRACTS mapping and
    fallback counters per dispatch site."""
    return paged_attention_ref(q, k_pool, v_pool, block_table, kv_len,
                               k_scale_pool, v_scale_pool, window=window,
                               tile=tile, m_rows=m_rows)


def channel_stats_ref(x: jax.Array):
    xf = x.astype(jnp.float32)
    return jnp.mean(xf, axis=0), jnp.var(xf, axis=0)


def quantize_pack_ref(w: jax.Array, scale: jax.Array, *, bits: int) -> jax.Array:
    q = quantize_values(w.astype(jnp.float32), scale.astype(jnp.float32), bits)
    return pack(q, bits)
