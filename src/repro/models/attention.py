"""Attention: GQA/MQA with full/causal/sliding-window/cross modes.

Long sequences use chunked online-softmax attention (flash-style, pure JAX
`lax.scan` over KV blocks) so prefill at 32k+ never materializes an (S, S)
score matrix. Decode uses a single-shot masked pass over the cache.

KV caches:
  * contiguous: {"k","v": (B, Smax, KVH, hd), "pos": (B, Smax) abs positions
    (-1 = empty), "len": (B,) fill counts}
  * sliding-window (Mixtral SWA): same structure with Smax = window; writes
    wrap modulo window (ring buffer), masking is driven by the "pos" array.
  * paged (continuous batching): {"k_pool","v_pool": (P, page, KVH, hd)};
    decode runs the fused Pallas paged-attention kernel by default
    (cfg.paged_attn_impl == "fused"): the kernel walks the slot's block
    table directly and dequantizes int8 K/V inline, so no gathered
    (S, maxp*page, ...) view is ever materialized. `paged_attn_impl ==
    "gather"` keeps the gather->dequant->einsum oracle path, which also
    serves paged *prefill* (see serve/kvcache.py, DESIGN.md
    "Paged-attention decode kernel").
RoPE is applied before cache insertion (post-rope keys are cached).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import TP_AXIS, lc
from repro.kernels.ops import (paged_attention, paged_attention_prefill,
                               paged_attention_verify)
from repro.models.config import ModelConfig
from repro.models.linear import dense, init_dense
from repro.models.rope import apply_rope
from repro.serve.kvcache import (PageSpec, contiguous_positions,
                                 gather_dequant_pages, gather_pages,
                                 prefill_page_index)

NEG = -1e30


def _mask(q_pos, kv_pos, *, causal: bool, window: Optional[int]):
    """q_pos: (B, Sq); kv_pos: (B, Skv) absolute positions (-1 = invalid)."""
    m = kv_pos[:, None, :] >= 0
    if causal:
        m &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        m &= kv_pos[:, None, :] > (q_pos[:, :, None] - window)
    return m  # (B, Sq, Skv)


def banded_attention(q, k, v, *, q_pos, kv_pos, window: int,
                     block_q: int = 1024):
    """Sliding-window self-attention that only computes the live band.

    Scans over q blocks; each block attends a (window + block_q)-wide key
    slice — O(S·window) compute/memory instead of O(S²) (the plain chunked
    path still *computes* fully-masked blocks). Requires sq == skv
    (aligned self-attention positions)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    bq = min(block_q, s)
    pad_q = (-s) % bq
    w = window
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    nb = (s + pad_q) // bq
    # pad keys with `w` dead slots in front so every slice is in-bounds
    kp = jnp.pad(k, ((0, 0), (w, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (w, 0), (0, 0), (0, 0)))
    pp = jnp.pad(kv_pos, ((0, 0), (w, 0)), constant_values=-1)

    def one_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        qpi = jax.lax.dynamic_slice_in_dim(q_pos, i * bq, bq, axis=1)
        ki = jax.lax.dynamic_slice_in_dim(kp, i * bq, w + bq, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(vp, i * bq, w + bq, axis=1)
        pi = jax.lax.dynamic_slice_in_dim(pp, i * bq, w + bq, axis=1)
        return attention_core(qi, ki, vi, q_pos=qpi, kv_pos=pi, causal=True,
                              window=w, block_kv=w + bq)

    out = jax.lax.map(one_block, jnp.arange(nb))        # (nb, B, bq, H, hdv)
    out = out.swapaxes(0, 1).reshape(b, nb * bq, h, v.shape[-1])
    return out[:, :s]


def attention_core(q, k, v, *, q_pos, kv_pos, causal=True,
                   window: Optional[int] = None, block_kv: int = 512,
                   banded: bool = False, chunked_decode: bool = False,
                   scores_dtype=jnp.float32, scale: Optional[float] = None):
    """q: (B,Sq,H,hd); k,v: (B,Skv,KVH,hd). Returns (B,Sq,H,hd). `scale`
    multiplies the scores (default 1/sqrt(hd))."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]  # may differ from hd (MLA)
    if (banded and causal and window is not None and sq == skv
            and sq > 2 * window):
        return banded_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                                window=window,
                                block_q=max(256, min(1024, window)))
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    single_shot = skv <= block_kv or (sq == 1 and not chunked_decode)
    if single_shot:
        # keep operands in their storage dtype (bf16 on TPU) and accumulate
        # in f32 via preferred_element_type — materializing an f32 copy of a
        # (gathered) KV cache doubles decode HBM/ICI traffic
        s = jnp.einsum("bqkgh,btkh->bkgqt", qg, k,
                       preferred_element_type=jnp.float32) * scale
        m = _mask(q_pos, kv_pos, causal=causal, window=window)
        s = jnp.where(m[:, None, None, :, :], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, sq, h, hd_v).astype(q.dtype)

    # chunked online softmax over KV blocks
    nblk = -(-skv // block_kv)
    pad = nblk * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    kc = k.reshape(b, nblk, block_kv, kvh, hd).swapaxes(0, 1)
    vc = v.reshape(b, nblk, block_kv, kvh, hd_v).swapaxes(0, 1)
    pc = kv_pos.reshape(b, nblk, block_kv).swapaxes(0, 1)

    def body(carry, xs):
        m_run, l_run, acc = carry
        kb, vb, pb = xs
        # scores materialize in `scores_dtype` (bf16 halves the dominant
        # HBM traffic); all reductions/accumulators stay f32
        s = jnp.einsum("bqkgh,btkh->bkgqt", qg, kb,
                       preferred_element_type=scores_dtype)
        s = (s.astype(jnp.float32)) * scale
        msk = _mask(q_pos, pb, causal=causal, window=window)
        s = jnp.where(msk[:, None, None, :, :], s, NEG)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqt,btkh->bkgqh", p.astype(scores_dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    # flash-attention memory behaviour: without this, scan-backward stacks
    # every step's (B,KVH,G,Sq,block) score tensor as residuals — O(S²)
    # saved activations; with it only the O(S·hd) carries are saved and
    # scores are recomputed per block in the backward pass
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    init = (jnp.full((b, kvh, g, sq), NEG, jnp.float32),
            jnp.zeros((b, kvh, g, sq), jnp.float32),
            jnp.zeros((b, kvh, g, sq, hd_v), jnp.float32))
    (m_run, l_run, acc), _ = jax.lax.scan(body, init, (kc, vc, pc))
    o = acc / jnp.maximum(l_run, 1e-30)[..., None]
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd_v)
    return o.astype(q.dtype)


# ---------------------------------------------------------------- GQA module

def init_attention(cfg: ModelConfig, key, *, cross: bool = False) -> dict:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d, h * hd, bias=cfg.qkv_bias, dtype=cfg.pdtype),
        "wk": init_dense(ks[1], d, kvh * hd, bias=cfg.qkv_bias, dtype=cfg.pdtype),
        "wv": init_dense(ks[2], d, kvh * hd, bias=cfg.qkv_bias, dtype=cfg.pdtype),
        "wo": init_dense(ks[3], h * hd, d, bias=cfg.o_bias, dtype=cfg.pdtype),
    }


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                  window: Optional[int] = None) -> dict:
    size = min(max_len, window) if window else max_len
    kvh, hd = cfg.n_kv_heads, cfg.hd
    cache = {
        "pos": jnp.full((batch, size), -1, jnp.int32),
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.kv_cache_bits == 8:
        # int8 storage with per-(token, head) scales: ~1.9x less HBM than
        # bf16 — beyond-paper extension of its low-bit deployment story
        cache["k"] = jnp.zeros((batch, size, kvh, hd), jnp.int8)
        cache["v"] = jnp.zeros((batch, size, kvh, hd), jnp.int8)
        cache["k_scale"] = jnp.zeros((batch, size, kvh), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, size, kvh), jnp.float32)
    else:
        cache["k"] = jnp.zeros((batch, size, kvh, hd), cfg.adtype)
        cache["v"] = jnp.zeros((batch, size, kvh, hd), cfg.adtype)
    return cache


def init_paged_kv_cache(cfg: ModelConfig, spec: PageSpec) -> dict:
    """Page-pool cache for one attention block (continuous batching).

    Sliding-window models still allocate full-length pages under paging;
    the window mask in attention_core keeps reads correct (see DESIGN.md).
    """
    kvh, hd = cfg.n_kv_heads, cfg.hd
    cache = {}
    if cfg.kv_cache_bits == 8:
        cache["k_pool"] = jnp.zeros((spec.n_pages, spec.page_size, kvh, hd),
                                    jnp.int8)
        cache["v_pool"] = jnp.zeros((spec.n_pages, spec.page_size, kvh, hd),
                                    jnp.int8)
        cache["k_scale_pool"] = jnp.zeros((spec.n_pages, spec.page_size, kvh),
                                          jnp.float32)
        cache["v_scale_pool"] = jnp.zeros((spec.n_pages, spec.page_size, kvh),
                                          jnp.float32)
    else:
        cache["k_pool"] = jnp.zeros((spec.n_pages, spec.page_size, kvh, hd),
                                    cfg.adtype)
        cache["v_pool"] = jnp.zeros((spec.n_pages, spec.page_size, kvh, hd),
                                    cfg.adtype)
    return cache


def _paged_update(cache: dict, k, v, positions, paged: dict):
    """Scatter new K/V into the page pool; return (new_cache, read view).

    Prefill (paged has "bt_rows"): writes a batch of admitted slots'
    (left-padded) prompts; the read view is the current sequence itself — a
    fresh request attends only to its own prompt. Chunked / prefix-suffix
    prefill (paged additionally has "kv_len": per-row total fill counts
    after this chunk) instead gathers the whole 0..kv_len-1 context back
    through the block-table rows, because earlier tokens live in pages the
    current call never saw — the slot's own earlier chunks, or shared
    prefix pages written by another request entirely. Decode (paged has
    "block_table"): writes one token per slot at (write_page, write_off),
    then gathers each slot's pages into a contiguous (S, width*page, ...)
    view for attention, with mask positions derived from the per-slot fill
    counts in paged["kv_len"]. Block tables passed for decode and chunked
    prefill may be truncated to the live read width (pow2 pages) by the
    engine.
    """
    new = dict(cache)
    quant = "k_scale_pool" in cache
    if "bt_rows" in paged:                          # prefill (batch of slots)
        bt = paged["bt_rows"]
        new = _paged_write_prefill(cache, k, v, positions, bt)
        if "kv_len" not in paged:           # fresh full prompt: self-attend
            return new, (k, v, positions)
        if quant:
            kg = gather_dequant_pages(new["k_pool"], new["k_scale_pool"],
                                      bt, k.dtype)
            vg = gather_dequant_pages(new["v_pool"], new["v_scale_pool"],
                                      bt, v.dtype)
        else:
            kg = gather_pages(new["k_pool"], bt)
            vg = gather_pages(new["v_pool"], bt)
        kv_pos = contiguous_positions(paged["kv_len"], kg.shape[1])
        return new, (kg, vg, kv_pos)
    bt = paged["block_table"]                                 # decode step
    new = _paged_write_decode(cache, k, v, paged)
    if quant:
        # one gather+dequant call per pool (see gather_dequant_pages)
        kg = gather_dequant_pages(new["k_pool"], new["k_scale_pool"], bt,
                                  k.dtype)
        vg = gather_dequant_pages(new["v_pool"], new["v_scale_pool"], bt,
                                  v.dtype)
    else:
        kg = gather_pages(new["k_pool"], bt)
        vg = gather_pages(new["v_pool"], bt)
    kv_pos = contiguous_positions(paged["kv_len"], kg.shape[1])
    return new, (kg, vg, kv_pos)


def _paged_write_prefill(cache: dict, k, v, positions, bt) -> dict:
    """Scatter a (B, S) batch of tokens at their block-table page slots
    (negative positions route to the reserved scratch page). Shared by the
    paged prefill path and the spec-decode verify write."""
    ps = cache["k_pool"].shape[1]
    pages, offs = prefill_page_index(bt, positions, ps)
    new = dict(cache)
    if "k_scale_pool" in cache:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        new["k_pool"] = cache["k_pool"].at[pages, offs].set(kq)
        new["v_pool"] = cache["v_pool"].at[pages, offs].set(vq)
        new["k_scale_pool"] = cache["k_scale_pool"].at[pages, offs].set(ks)
        new["v_scale_pool"] = cache["v_scale_pool"].at[pages, offs].set(vs)
    else:
        new["k_pool"] = cache["k_pool"].at[pages, offs].set(
            k.astype(cache["k_pool"].dtype))
        new["v_pool"] = cache["v_pool"].at[pages, offs].set(
            v.astype(cache["v_pool"].dtype))
    return new


def _paged_write_decode(cache: dict, k, v, paged: dict) -> dict:
    """Scatter one decode token per slot at (write_page, write_off).

    Shared by the fused-kernel and gather decode paths — the fused path
    stops here and hands the pools straight to kernels/paged_attention."""
    new = dict(cache)
    wp, wo = paged["write_page"], paged["write_off"]
    if "k_scale_pool" in cache:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        new["k_pool"] = cache["k_pool"].at[wp, wo].set(kq[:, 0])
        new["v_pool"] = cache["v_pool"].at[wp, wo].set(vq[:, 0])
        new["k_scale_pool"] = cache["k_scale_pool"].at[wp, wo].set(ks[:, 0])
        new["v_scale_pool"] = cache["v_scale_pool"].at[wp, wo].set(vs[:, 0])
    else:
        new["k_pool"] = cache["k_pool"].at[wp, wo].set(
            k[:, 0].astype(cache["k_pool"].dtype))
        new["v_pool"] = cache["v_pool"].at[wp, wo].set(
            v[:, 0].astype(cache["v_pool"].dtype))
    return new


def _quant_kv(x: jax.Array):
    """x: (B, S, KVH, hd) -> (int8 values, (B, S, KVH) scales)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1), 1e-6)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _cache_write(cache: dict, k, v, positions) -> dict:
    """Write S new entries at ring slots positions % size.

    If S exceeds the ring size (SWA prefill longer than the window), only the
    last `size` entries are written — older ones could never be attended to,
    and truncating keeps ring slots unique within one scatter.
    """
    b, s = positions.shape
    size = cache["k"].shape[1]
    if s > size:
        k, v, positions = k[:, -size:], v[:, -size:], positions[:, -size:]
        s = size
    slots = positions % size                                   # (B, S)
    new = dict(cache)
    bidx = jnp.arange(b)[:, None]
    if "k_scale" in cache:  # int8 cache
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        new["k"] = cache["k"].at[bidx, slots].set(kq)
        new["v"] = cache["v"].at[bidx, slots].set(vq)
        new["k_scale"] = cache["k_scale"].at[bidx, slots].set(ks)
        new["v_scale"] = cache["v_scale"].at[bidx, slots].set(vs)
    else:
        new["k"] = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype))
        new["v"] = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype))
    new["pos"] = cache["pos"].at[bidx, slots].set(positions)
    new["len"] = cache["len"] + s
    return new


def apply_attention(cfg: ModelConfig, p: dict, x: jax.Array, *,
                    positions: jax.Array, causal: bool = True,
                    window: Optional[int] = None,
                    cache: Optional[dict] = None,
                    kv_src: Optional[jax.Array] = None,
                    kv_positions: Optional[jax.Array] = None,
                    rope_variant: Optional[str] = None,
                    paged: Optional[dict] = None,
                    taps: Optional[dict] = None, tap_prefix: str = ""):
    """Returns (y, new_cache). `kv_src` => cross-attention (no rope/cache-write
    unless cache holds precomputed cross K/V under k/v). `paged` carries the
    block-table indices for a paged cache (see serve/kvcache.py)."""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rope_variant = rope_variant if rope_variant is not None else cfg.rope

    if taps is not None:
        taps[tap_prefix + "wq"] = x
        if kv_src is None:
            taps[tap_prefix + "wk"] = x
            taps[tap_prefix + "wv"] = x
        else:
            taps[tap_prefix + "wk"] = kv_src
            taps[tap_prefix + "wv"] = kv_src

    q = dense(p["wq"], x).reshape(b, s, h, hd)
    q = lc(q, "batch", "seq", "heads", "head_dim")
    q = apply_rope(q, positions, theta=cfg.rope_theta, variant=rope_variant)

    fused_o = None
    if (cache is not None and "len" not in cache and "k_pool" not in cache
            and kv_src is None):
        # precomputed cross-attention K/V (whisper decode)
        k, v = cache["k"], cache["v"]
        kv_pos = cache["pos"]
        new_cache = cache
    else:
        src = kv_src if kv_src is not None else x
        kv_b, kv_s = src.shape[0], src.shape[1]
        k = dense(p["wk"], src).reshape(kv_b, kv_s, kvh, hd)
        v = dense(p["wv"], src).reshape(kv_b, kv_s, kvh, hd)
        kpos = kv_positions if kv_positions is not None else positions
        k = apply_rope(k, kpos, theta=cfg.rope_theta, variant=rope_variant)
        if (cache is not None and "k_pool" in cache
                and paged is not None and "block_table" in paged
                and s == 1 and cfg.paged_attn_impl == "fused"):
            # fused paged decode: scatter the new token into the pools, then
            # walk the block table *inside* the kernel — int8 K/V dequantized
            # inline from the scale pools, no gathered (S, maxp*page, ...)
            # view in HBM, dead pages never read
            new_cache = _paged_write_decode(cache, k, v, paged)
            fused_o = paged_attention(
                q[:, 0], new_cache["k_pool"], new_cache["v_pool"],
                paged["block_table"], paged["kv_len"],
                k_scale_pool=new_cache.get("k_scale_pool"),
                v_scale_pool=new_cache.get("v_scale_pool"),
                window=window, out_dtype=q.dtype)[:, None]
        elif (cache is not None and "k_pool" in cache
                and paged is not None and "verify" in paged
                and cfg.paged_attn_impl == "fused"):
            # spec-decode verify: scatter the s tail tokens with the prefill
            # scatter (inactive slots carry positions < 0 and route to the
            # scratch page), then read all s rows in one fused page walk
            # with per-row causal fill masks — each live KV tile streams
            # once for the whole verify batch
            new_cache = _paged_write_prefill(cache, k, v, kpos,
                                             paged["bt_rows"])
            fused_o = paged_attention_verify(
                q, new_cache["k_pool"], new_cache["v_pool"],
                paged["bt_rows"], paged["kv_len"],
                k_scale_pool=new_cache.get("k_scale_pool"),
                v_scale_pool=new_cache.get("v_scale_pool"),
                window=window, out_dtype=q.dtype)
        elif (cache is not None and "k_pool" in cache
                and paged is not None and "bt_rows" in paged
                and "kv_len" in paged and causal
                and cfg.paged_attn_impl == "fused"):
            # fused chunked/suffix prefill: scatter the left-padded chunk
            # with the prefill scatter (pad rows carry positions < 0 and
            # route to the scratch page), then read all s rows in one
            # fused page walk — row j sits at fill position kv_len - s + j
            # exactly like a verify row, so earlier context (prior chunks,
            # shared prefix pages) streams through the page walk instead
            # of being gathered into a contiguous HBM view
            new_cache = _paged_write_prefill(cache, k, v, kpos,
                                             paged["bt_rows"])
            fused_o = paged_attention_prefill(
                q, new_cache["k_pool"], new_cache["v_pool"],
                paged["bt_rows"], paged["kv_len"],
                k_scale_pool=new_cache.get("k_scale_pool"),
                v_scale_pool=new_cache.get("v_scale_pool"),
                window=window, out_dtype=q.dtype)
        elif cache is not None and "k_pool" in cache:
            # paged cache (continuous batching): scatter new K/V into the
            # page pool, read back via the slot block tables
            assert paged is not None, \
                "paged cache requires block-table indices"
            new_cache, (k, v, kv_pos) = _paged_update(cache, k, v, kpos,
                                                      paged)
        elif cache is not None and "len" not in cache:
            # cross-attention cache fill (enc-dec prefill)
            new_cache = {"k": k.astype(cache["k"].dtype),
                         "v": v.astype(cache["v"].dtype), "pos": kpos}
            kv_pos = kpos
        elif cache is not None:
            new_cache = _cache_write(cache, k, v, kpos)
            if s == 1:
                # decode: attend over the whole (ring) cache
                if "k_scale" in new_cache:
                    k = _dequant_kv(new_cache["k"], new_cache["k_scale"],
                                    x.dtype)
                    v = _dequant_kv(new_cache["v"], new_cache["v_scale"],
                                    x.dtype)
                else:
                    k, v = new_cache["k"], new_cache["v"]
                kv_pos = new_cache["pos"]
            else:
                # one-shot prefill: attend over the current sequence directly
                # (a ring cache may already have evicted early positions that
                # early queries still need; the banded mask handles windowing)
                kv_pos = kpos
        else:
            new_cache = None
            kv_pos = kpos
    if fused_o is not None:
        o = fused_o                                        # (B, 1, H, hd_v)
    else:
        k = lc(k, "batch", "kv_seq", "kv_heads", "head_dim")
        v = lc(v, "batch", "kv_seq", "kv_heads", "head_dim")
        o = attention_core(q, k, v, q_pos=positions, kv_pos=kv_pos,
                           causal=causal, window=window,
                           block_kv=cfg.attn_block_kv,
                           banded=cfg.banded_window_attn,
                           chunked_decode=cfg.chunked_decode,
                           scores_dtype=jnp.dtype(cfg.attn_scores_dtype))
    o = o.reshape(b, s, h * hd)
    if taps is not None:
        taps[tap_prefix + "wo"] = o
    # under serving TP (cfg.tp > 1, inside the engine's shard_map) the
    # output projection is row-parallel: each shard holds its heads' slice
    # of wo, so the matmul is a partial sum reduced over the model axis
    y = dense(p["wo"], o, reduce_axis=TP_AXIS if cfg.tp > 1 else None)
    return lc(y, "batch", "seq", "embed"), new_cache
