"""Declarative Pallas kernel templates shared by every quantized kernel.

The four serving kernels (dense dequant matmul, expert-batched dequant,
W8A8/W4A8 int8 MXU, paged attention) share one structure: walk a packed
operand per `pack_layout`, rescale per scale group, fold each tile into an
accumulator (plain f32 add for matmuls, online softmax for attention).
This module is the single place that structure lives. A kernel module
declares a spec — :class:`MatmulSpec` (grid shape, packed-walk params,
epilogue) or :class:`PagedSpec` (page geometry, window, verify rows) — and
asks the builders here for the kernel body + block specs; only the
`pl.pallas_call` site stays in the kernel module (so the RL004 contract
registry keeps one wrapper-per-kernel granularity).

The generated bodies perform the *identical op sequence* the handwritten
kernels used — same unpack shifts, same dot/accumulate order, same mask
and softmax updates — so interpret-mode runs stay bit-comparable with the
jnp references in `kernels/ref.py` and the parity matrix pins the refactor.

`TEMPLATE_VERSION` is a content hash of this file; the autotune cache
(kernels/autotune.py) embeds it in its on-disk format so tile configs
measured against an older template generation are ignored, not replayed.

Epilogues:
  * "dequant_bf16": unpack -> per-group f32 scale -> bf16 MXU dot,
    f32 accumulate (weight-only serving path).
  * "int8_mxu": unpack to int8 values -> one int8 x int8 -> int32 MXU dot
    per scale group -> f32 rescale-accumulate (FPTQ-style W4A8/W8A8; the
    per-token activation scale is applied by the caller).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import pathlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant.types import pack_layout, qmax_for_bits

NEG = -1e30


def _template_version() -> str:
    src = pathlib.Path(__file__.replace(".pyc", ".py")).read_bytes()
    return hashlib.sha256(src).hexdigest()[:16]


TEMPLATE_VERSION = _template_version()


# ------------------------------------------------------- packed-operand walk

def packed_tile_rows(bk: int, bits: int) -> int:
    """uint8 rows of a packed tile holding bk values (bk % vpg == 0)."""
    bpg, vpg = pack_layout(bits)
    assert bk % vpg == 0, (bk, bits)
    return bk // vpg * bpg


def unpack_tile(qw: jax.Array, bits: int, bk: int) -> jax.Array:
    """(packed_tile_rows(bk), bn) packed uint8 tile -> (bk, bn) int32 values
    in [-qmax, qmax]. Lane-local shift/mask unpack (packing is along K, rows
    interleave as r*vpg+i), shared by every dequant-style kernel."""
    bpg, vpg = pack_layout(bits)
    qmax = qmax_for_bits(bits)
    bn = qw.shape[-1]
    if (bpg, vpg) == (1, 1):
        u = qw
    else:
        # shift in int32: Mosaic has no vector shifts on 8-bit lanes, and
        # every packed word (<= 24 bits) is non-negative, so arithmetic
        # and logical shifts agree
        if bpg == 1:
            word = qw.astype(jnp.int32)
        else:
            # multi-byte group (W3): rebuild the little-endian word first
            grp = qw.astype(jnp.int32).reshape(bk // vpg, bpg, bn)
            word = grp[:, 0, :]
            for b in range(1, bpg):
                word = word | (grp[:, b, :] << (8 * b))
        mask = (1 << bits) - 1
        parts = [(word >> (bits * i)) & mask for i in range(vpg)]
        u = jnp.stack(parts, axis=1).reshape(bk, bn)
    return u.astype(jnp.int32) - qmax


def scale_tile(q: jax.Array, s: jax.Array, bk: int) -> jax.Array:
    """Apply a (gb, bn) group-scale block to a (bk, bn) int tile -> f32."""
    gb, bn = s.shape
    if gb == 1:
        return q.astype(jnp.float32) * s
    return (q.reshape(gb, bk // gb, bn).astype(jnp.float32) *
            s[:, None, :]).reshape(bk, bn)


def scale_rows(k: int, g: int, bk: int) -> int:
    """Scale rows one (bk, bn) K tile needs: bk // group_size when a tile
    holds several groups, else 1 (per-channel, or a group spans tiles)."""
    return max(1, bk * g // k)


def scale_operand(scale: jax.Array, *, k: int, bk: int) -> jax.Array:
    """(..., G, N) scales -> (..., G // r, r, N) f32 with r = scale_rows.

    Mosaic takes a block only when its last two dims are multiples of
    (8, 128) or span the array; a (r, bn) slice of a (G, N) scale is
    neither once 1 < G. Splitting G so that each K tile's r rows are a
    whole trailing (r, N) plane keeps the block legal for every group
    size without any in-kernel dynamic slicing."""
    g, n = scale.shape[-2:]
    r = scale_rows(k, g, bk)
    return scale.astype(jnp.float32).reshape(
        scale.shape[:-2] + (g // r, r, n))


def scale_blockspec(k: int, g: int, bk: int, bn: int):
    """BlockSpec walking a `scale_operand` alongside (bk, bn) K tiles: K
    step kk reads the r scale rows of K range [kk*bk, (kk+1)*bk) — whole
    groups per block (r > 1), or the one group the block sits in (r == 1,
    including per-channel)."""
    r = scale_rows(k, g, bk)
    gs = k // g
    return pl.BlockSpec((1, r, bn),
                        lambda i, j, kk: (kk * bk // (gs * r), 0, j))


# --------------------------------------------------------- matmul templates

@dataclasses.dataclass(frozen=True)
class MatmulSpec:
    """One packed-matmul kernel variant.

    grouped: rows arrive in expert groups of whole row tiles, and a
    scalar-prefetched tile -> expert map picks the stacked (E, ...) packed
    slab each row tile multiplies (`grouped_grid_spec`).
    epilogue: accumulate stage (see module docstring).
    """
    name: str
    epilogue: str = "dequant_bf16"
    grouped: bool = False

    def __post_init__(self):
        assert self.epilogue in ("dequant_bf16", "int8_mxu"), self.epilogue


def make_matmul_kernel(spec: MatmulSpec, *, bits: int, bk: int):
    """Kernel body for `spec`: zero-init on the first K step, unpack the
    packed tile, run the epilogue's dot(s), accumulate into the output
    tile. Op-for-op identical to the former handwritten bodies. A grouped
    body skips the dots of row tiles past the live count, whose output
    stays zero."""

    def accumulate(x, qw, s, o_ref):
        q = unpack_tile(qw, bits, bk)                  # (bk, bn) int32
        if spec.epilogue == "dequant_bf16":
            w = scale_tile(q, s, bk)                   # (bk, bn) f32
            acc = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
            o_ref[...] += acc
        else:  # int8_mxu
            # unpacked values always fit int8 (|q| <= 127), so the MXU dots
            # below run int8 x int8 -> int32 for any packed bits
            w8 = q.astype(jnp.int8)                    # (bk, bn)
            gb = s.shape[0]
            gsb = bk // gb
            acc = o_ref[...]
            for gi in range(gb):
                d = jnp.dot(x[:, gi * gsb:(gi + 1) * gsb],
                            w8[gi * gsb:(gi + 1) * gsb],
                            preferred_element_type=jnp.int32)
                acc = acc + d.astype(jnp.float32) * s[gi][None, :]
            o_ref[...] = acc

    if spec.grouped:
        def kernel(tile_expert_ref, n_live_ref, x_ref, qw_ref, scale_ref,
                   o_ref):
            @pl.when(pl.program_id(2) == 0)
            def _init():
                o_ref[...] = jnp.zeros_like(o_ref)

            @pl.when(pl.program_id(0) < n_live_ref[0])
            def _live():
                accumulate(x_ref[...], qw_ref[0], scale_ref[0, 0], o_ref)
    else:
        def kernel(x_ref, qw_ref, scale_ref, o_ref):
            @pl.when(pl.program_id(2) == 0)
            def _init():
                o_ref[...] = jnp.zeros_like(o_ref)

            accumulate(x_ref[...], qw_ref[...], scale_ref[0], o_ref)

    kernel.__name__ = f"_{spec.name}_kernel"
    return kernel


def matmul_grid(spec: MatmulSpec, *, m: int, n: int, k: int, bm: int,
                bn: int, bk: int):
    return (m // bm, n // bn, k // bk)


def matmul_in_specs(spec: MatmulSpec, *, bits: int, k: int, g: int, bm: int,
                    bn: int, bk: int):
    """[x, packed qw, scale] block specs for the (M, N, K) grid walk."""
    return [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((packed_tile_rows(bk, bits), bn),
                     lambda i, j, kk: (kk, j)),
        scale_blockspec(k, g, bk, bn),
    ]


def matmul_out_spec(spec: MatmulSpec, *, bm: int, bn: int):
    return pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))


def grouped_grid_spec(spec: MatmulSpec, *, bits: int, n_tiles: int, n: int,
                      k: int, g: int, bm: int, bn: int, bk: int):
    """PrefetchScalarGridSpec of the grouped walk over (row tile, N/bn,
    K/bk), K innermost, with the tile -> expert map and the live tile
    count scalar-prefetched. Row tile i multiplies the packed slab of
    expert tile_expert[i], so a call streams only the weight blocks of
    experts that hold rows. Tiles from the live count on are dead: their
    index maps stay on the last live step's blocks, so they start no
    DMA, and their bodies skip the dot."""
    nk, nn = k // bk, n // bn
    r = scale_rows(k, g, bk)
    gs = k // g

    def walk(i, j, kk, n_live):
        live = i < n_live[0]
        return (jnp.where(live, i, jnp.maximum(n_live[0] - 1, 0)),
                jnp.where(live, j, nn - 1), jnp.where(live, kk, nk - 1))

    def x_map(i, j, kk, te, nl):
        i_, _, kk_ = walk(i, j, kk, nl)
        return (i_, kk_)

    def w_map(i, j, kk, te, nl):
        _, j_, kk_ = walk(i, j, kk, nl)
        return (te[i], kk_, j_)

    def s_map(i, j, kk, te, nl):
        _, j_, kk_ = walk(i, j, kk, nl)
        return (te[i], kk_ * bk // (gs * r), 0, j_)

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nn, nk),
        in_specs=[pl.BlockSpec((bm, bk), x_map),
                  pl.BlockSpec((1, packed_tile_rows(bk, bits), bn), w_map),
                  pl.BlockSpec((1, 1, r, bn), s_map)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, te, nl: (i, j)),
    )


# ------------------------------------------------- paged-attention template

@dataclasses.dataclass(frozen=True)
class PagedSpec:
    """One paged-attention page-walk variant: decode (m_rows == 1), verify
    and chunked prefill (m_rows > 1) are the same walk with per-row causal
    fill limits; `quant` adds the int8 scale-pool operands."""
    page_size: int
    tile: int
    window: Optional[int]
    m_rows: int
    quant: bool

    def __post_init__(self):
        assert self.page_size % self.tile == 0, (self.page_size, self.tile)


def _tile_coords(t: jax.Array, *, page_size: int, tile: int):
    """Grid step t on the page-walk axis -> (page slot w, sub-tile, base pos)."""
    nt = page_size // tile
    w = t // nt
    sub = t % nt
    base = w * page_size + sub * tile
    return w, sub, base


def tile_live(spec: PagedSpec, s, t, bt, kl):
    """Does grid step t hold any live (unmasked) token for slot s?

    Dead tiles are skipped entirely: beyond the fill count, on an unheld
    block-table entry (-1), or — with sliding-window attention — wholly
    behind the window. This predicate is shared by the index maps (route
    the DMA to the scratch page) and the kernel body (skip the compute).

    With ``m_rows`` query rows the earliest row's window starts at
    ``kl - (m_rows - 1) - window``, so the SWA liveness bound loosens by
    exactly ``m_rows - 1`` tokens (rows that reach further back than a
    given tile mask it per-row inside the kernel).
    """
    w, _, base = _tile_coords(t, page_size=spec.page_size, tile=spec.tile)
    live = (base < kl[s]) & (bt[s, w] >= 0)
    if spec.window is not None:
        live &= (base + spec.tile) > (kl[s] - (spec.m_rows - 1) - spec.window)
    return live


def page_map(spec: PagedSpec, ndim: int):
    """Index map for the pool tiles of grid cell (s, t): the page-walk
    step's page (the scratch page when dead) and sub-tile, every kv head.
    ndim 4 walks the K/V pools, ndim 3 their int8 scale pools."""
    def index(s, t, bt, kl):
        w, sub, _ = _tile_coords(t, page_size=spec.page_size, tile=spec.tile)
        live = tile_live(spec, s, t, bt, kl)
        page = jnp.where(live, jnp.maximum(bt[s, w], 0), 0)
        return (page, sub) + (0,) * (ndim - 2)
    return index


def make_paged_kernel(spec: PagedSpec, *, sm_scale: float, n_steps: int):
    """Online-softmax page-walk body: init scratch on the first step, fold
    each live KV tile into the (m, l, acc) accumulators with per-row causal
    fill limits, finalize with the guarded divide on the last step."""
    page_size, tile, window, m_rows = (spec.page_size, spec.tile,
                                       spec.window, spec.m_rows)

    def kernel(bt_ref, kl_ref, q_ref, k_ref, v_ref, *rest):
        if spec.quant:
            ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
        else:
            o_ref, m_scr, l_scr, acc_scr = rest
        s_i = pl.program_id(0)
        t_i = pl.program_id(1)
        kl = kl_ref[s_i]
        _, _, base = _tile_coords(t_i, page_size=page_size, tile=tile)
        live = tile_live(spec, s_i, t_i, bt_ref, kl_ref)

        @pl.when(t_i == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        def _fold_head(h):
            q = q_ref[0, h].astype(jnp.float32)              # (R, hd)
            k = k_ref[0, :, h, :]                            # (tile, hd)
            v = v_ref[0, :, h, :]                            # (tile, hd_v)
            if spec.quant:
                kf = k.astype(jnp.float32) * ks_ref[0][:, h:h + 1]
                vf = v.astype(jnp.float32) * vs_ref[0][:, h:h + 1]
            else:
                kf = k.astype(jnp.float32)
                vf = v.astype(jnp.float32)
            s = jax.lax.dot_general(q, kf, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * sm_scale                                 # (R, tile)
            pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            rows = q.shape[0]                                # R = m_rows * G
            g = rows // m_rows
            # row r holds the token at fill position kl - m_rows + r//g, so
            # its causal limit is kl - (m_rows - 1 - r//g); at m_rows == 1
            # this is the scalar kl of the decode read
            r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            lim = kl - (m_rows - 1 - r // g)
            valid = pos < lim
            if window is not None:
                valid &= pos > (lim - 1 - window)
            s = jnp.where(valid, s, NEG)
            m_prev = m_scr[h]                                # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                           # (R, tile)
            # a live tile can sit wholly outside an *early* row's reach
            # (m_rows > 1); that row's m_new is still NEG there, making
            # exp(NEG - NEG) garbage — zero masked columns explicitly. At
            # m_rows == 1 every live tile has a valid column, m_new > NEG,
            # and masked columns underflow to exactly 0.0 anyway:
            # bit-identical.
            p = jnp.where(valid, p, 0.0)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                p, vf, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

        @pl.when(live)
        def _compute():
            # one (tile, KVH, hd) block carries every kv head of the page
            # tile (the only block of the (P, page, KVH, hd) pool Mosaic
            # accepts); each head folds its own slice into its own
            # accumulators, in the walk order of the per-head reference
            for h in range(q_ref.shape[1]):
                _fold_head(h)

        @pl.when(t_i == n_steps - 1)
        def _finalize():
            # empty slots (kv_len == 0) never accumulate: l stays 0 and the
            # guarded divide emits exact zeros (the engine discards them)
            o_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)

    return kernel


def paged_grid_spec(spec: PagedSpec, *, s: int, kvh: int, rows: int, hd: int,
                    hd_v: int, n_steps: int):
    """PrefetchScalarGridSpec for the (S, page-walk) grid: block table +
    fill counts scalar-prefetched so index maps chase page ids before each
    tile's DMA, per-kv-head (m, l, acc) accumulators in VMEM scratch. Each
    pool block spans all KVH heads of one page tile, so its last two dims
    equal the pool's and every tile streams once per slot."""
    tile = spec.tile
    in_specs = [
        pl.BlockSpec((1, kvh, rows, hd),
                     lambda s_, t_, bt, kl: (s_, 0, 0, 0)),
        pl.BlockSpec((1, tile, kvh, hd), page_map(spec, 4)),
        pl.BlockSpec((1, tile, kvh, hd_v), page_map(spec, 4)),
    ]
    if spec.quant:
        in_specs += [
            pl.BlockSpec((1, tile, kvh), page_map(spec, 3)),
            pl.BlockSpec((1, tile, kvh), page_map(spec, 3)),
        ]
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, rows, hd_v),
                               lambda s_, t_, bt, kl: (s_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, rows, 1), jnp.float32),      # running max
            pltpu.VMEM((kvh, rows, 1), jnp.float32),      # running denominator
            pltpu.VMEM((kvh, rows, hd_v), jnp.float32),   # output accumulator
        ],
    )
