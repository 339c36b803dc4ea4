"""Tile-regime selection for every Pallas kernel: a deterministic fallback
table plus a measured autotuner with a persistent JSON config cache.

Every kernel dispatch in `kernels/ops.py` asks this module for its tile
plan. Resolution order, governed by ``REPRO_AUTOTUNE`` (debug_flags):

  * ``"0"``        — always the deterministic fallback table. CI and the
    compile-count sanitizer run here implicitly: with no cache file the default mode degrades to the
    table, so replay-twice sees identical plans and zero new tracings.
  * ``""`` (default) — a warm cache entry for the shape class if the JSON
    cache (``REPRO_AUTOTUNE_CACHE``) is readable and was written by this
    template generation; else the table.
  * ``"1"``        — measure real ``pallas_call`` candidates for a cold
    shape class, record the winner in-process, and persist it when a cache
    path is set.

Shape classes bucket the token dim (decode-skinny M <= 8 collapses to one
class, larger Ms to pow2 buckets) and key on everything that changes the
kernel's inner loop: kind, N, K, bits, group_size for matmuls;
page_size, KV dtype, m-rows bucket for the paged-attention walk.

Cache hygiene: the on-disk format embeds `template.TEMPLATE_VERSION` (a
content hash of kernels/template.py), so configs measured against an older
template generation are ignored wholesale; corrupt or unreadable files log
a warning and fall back to the table; individual entries are re-validated
against the kernel's tiling constraints before use, so a hand-edited or
stale entry can never reach a `pallas_call` that would reject it.
"""
from __future__ import annotations

import json
import logging
import os
import time
from typing import Optional

import jax
import numpy as np

from repro import debug_flags
from repro.core.quant.types import pack_layout
from repro.kernels import template

_LOG = logging.getLogger(__name__)

# ------------------------------------------------ deterministic fallback
# (the plans the serving stack gets with a cold cache or REPRO_AUTOTUNE=0)

# decode-shaped tiles of the int8 kind: minimal token rows, wide weight
# tiles
_SKINNY_M = 8
_SKINNY_BN = 512
_SKINNY_BK = 512

# dense dequant at one row tile (padded M <= _ROW_TILE_M: every decode call
# of up to 128 slots, and prefill calls as small): the whole token dim
# rides in one block, and the weight block takes the most bk*bn values
# under one budget. At 256x256 targets a decode call spent about half its
# time in the fixed cost of its grid steps; on a v5e, at the benchmark's
# W4 and W3 widths, blocks larger than 2**19 values were no faster
# (PERF.md section 5)
_ROW_TILE_M = 128
_DEQUANT_BLOCK_VALUES = 1 << 19

# paged-attention read-width regime: the page walk streams one KV tile per
# grid step; small pages ride whole (the common serving geometry — page_size
# 16/32 — is far below the cap), oversized pages split into <=256-token
# sub-tiles so a step's K/V/score working set stays VMEM-resident instead of
# scaling with page_size
_PAGE_TILE = 256


def pick_block(dim: int, target: int) -> int:
    if dim <= target:
        return dim
    b = target
    while dim % b != 0:
        b //= 2
        if b < 8:
            return dim  # fall back to a single block
    return b


def pick_bk(k: int, gs: int, vpg: int, target: int) -> Optional[int]:
    """K block size that divides K, packs whole byte groups (vpg values per
    `pack_layout` group), and tiles the scale groups (whole groups per
    block, or whole blocks per group). Returns None when no such block
    exists — e.g. a group size with a large odd factor — so callers can
    fall back to the jnp reference instead of spinning the shrink loop
    down to a mod-by-zero."""
    if gs == k:
        # per-channel (n_groups == 1): the group constraint collapses to
        # bk | k, so any divisor of K that packs whole byte groups works.
        # The halving loop below could only ever return K itself here (or
        # give up): target halvings rarely divide a non-pow2 K, and the
        # "whole blocks per group" branch needs bk | k anyway. Take the
        # largest such divisor <= target directly.
        if k % vpg != 0:
            return None
        for d in range(min(target, k), 7, -1):
            if k % d == 0 and d % vpg == 0:
                return d
        return k  # no >= 8-row divisor under target: one whole-K block
    bk = pick_block(k, target)
    while k % bk != 0 or (gs < bk and bk % gs != 0) or \
            (gs >= bk and gs % bk != 0) or bk % vpg != 0:
        bk //= 2  # halving can break K-divisibility; re-checked above
        if bk < max(vpg, 1):
            return None
    return bk


def matmul_blocks(m: int, bm: int, bn: int, bk: int):
    """Prefill-vs-decode tile regime: skinny token counts trade token-dim
    padding for wider weight tiles."""
    if m <= _SKINNY_M:
        return _SKINNY_M, max(bn, _SKINNY_BN), max(bk, _SKINNY_BK)
    return bm, bn, bk


# kinds whose calls run at one row tile: the dense dequant matmul up to
# _ROW_TILE_M rows, and the grouped expert kinds, whose m is the row tile
# `grouped_row_tile` gives (the weight block walks the expert's slab)
_ROW_TILE_KINDS = ("dequant", "grouped_expert", "grouped_w8a8")


def grouped_row_tile(rows: int, groups: int) -> int:
    """Row tile of the grouped expert kernels for `rows` (token, expert)
    pairs over `groups` experts: the power of two from 16 up that reaches
    twice the mean rows an expert gets, capped at _ROW_TILE_M. Most
    experts' rows then fit one tile, so each hit expert's weights stream
    once a call (64 decode slots at top-6 of 64 experts: 6 rows on
    average, 16-row tiles)."""
    bm = 16
    while bm < min(2.0 * rows / groups, _ROW_TILE_M):
        bm *= 2
    return bm


def _table_plan(kind: str, m: int, k: int, n: int, *, bits: int,
                group_size: int, bm: int, bn: int, bk: int):
    """(plan, regime name) from the deterministic table: the row-tile
    kinds at one row tile take `row_tile_plan`; every other call (and a
    shape no row-tile plan fits) takes the regime by token count."""
    if kind in _ROW_TILE_KINDS and m <= _ROW_TILE_M:
        plan = row_tile_plan(m, k, n, bits=bits, group_size=group_size)
        if plan is not None:
            return plan, "row_tile"
    regime = "skinny" if m <= _SKINNY_M else "prefill"
    gs = group_size if group_size != -1 else k
    vpg = pack_layout(bits)[1]
    bm, bn, bk = matmul_blocks(m, bm, bn, bk)
    bk_ = pick_bk(k, gs, vpg, bk)
    if bk_ is None:
        return None, regime
    return (pick_block(max(m, 8), bm), lane_block(n, bn), bk_), regime


def lane_block(n: int, target: int) -> int:
    """The N block of a table plan: the largest lane tile of N (a multiple
    of 128 that divides it, or N whole) up to `target`, else its smallest
    lane tile (DeepSeek-V2's latent projection, N = 576, takes it whole;
    halving would give 64 lanes, which Mosaic refuses)."""
    tiles = _lane_tiles(n)
    fit = [t for t in tiles if t <= target]
    return max(fit) if fit else tiles[0]


def fallback_matmul_plan(m: int, k: int, n: int, *, kind: str, bits: int,
                         group_size: int, bm: int, bn: int, bk: int):
    """Tile regime by kernel kind and token count, then concrete
    (bm, bn, bk) blocks. Returns None when K admits no valid block —
    callers fall back to the jnp ref."""
    return _table_plan(kind, m, k, n, bits=bits, group_size=group_size,
                       bm=bm, bn=bn, bk=bk)[0]


def _lane_tiles(dim: int) -> list[int]:
    """Divisors of `dim` a block may take along a lane axis: multiples of
    128, or the whole dim."""
    return sorted({d for d in range(128, dim, 128) if dim % d == 0} | {dim})


def row_tile_plan(m: int, k: int, n: int, *, bits: int, group_size: int):
    """(bm, bn, bk) for the dense dequant kernel when the token dim fits
    one row tile: bm = M padded to 8, and the legal (bn, bk) holding the
    most weight values with bk * bn <= _DEQUANT_BLOCK_VALUES; of equal
    sizes, the wider N block. Divisors are searched directly (halving
    would turn K = 128 * 107 into 128). Legal: bk divides K, packs whole
    `pack_layout` groups into whole 32-row uint8 tiles, tiles the scale
    groups and is a lane multiple (or K); bn divides N and is a lane
    multiple (or N). None when no plan fits the budget."""
    gs = group_size if group_size != -1 else k
    bpg, vpg = pack_layout(bits)
    bks = [b for b in _lane_tiles(k)
           if b % vpg == 0 and (b == k or b // vpg * bpg % 32 == 0) and
           ((gs >= b and gs % b == 0) or (gs < b and b % gs == 0))]
    best = max(((bk * bn, bn, bk) for bk in bks for bn in _lane_tiles(n)
                if bk * bn <= _DEQUANT_BLOCK_VALUES), default=None)
    if best is None:
        return None
    return -(-max(m, 1) // 8) * 8, best[1], best[2]


def fallback_paged_tile(page_size: int) -> int:
    """Token tile per page-walk step (read-width regime, see _PAGE_TILE)."""
    return pick_block(page_size, _PAGE_TILE)


# ------------------------------------------------------- shape-class keys

def m_bucket(m: int) -> int:
    """Token-dim bucket: decode-skinny Ms collapse to one class, larger Ms
    to the next power of two (the engine pads to pow2 buckets anyway)."""
    if m <= _SKINNY_M:
        return _SKINNY_M
    b = 16
    while b < m:
        b *= 2
    return b


def matmul_key(kind: str, m: int, k: int, n: int, bits: int,
               group_size: int) -> str:
    return f"{kind}:m{m_bucket(m)}:n{n}:k{k}:w{bits}:g{group_size}"


def paged_key(page_size: int, kv_dtype: str, m_rows: int) -> str:
    return f"paged:ps{page_size}:kv{kv_dtype}:m{m_bucket(m_rows)}"


# ------------------------------------------------------------ cache state

# in-memory view of the JSON cache, keyed by the path it was loaded from;
# measured winners land here too (and on disk when a path is set)
_state: dict = {"path": None, "entries": None}

# every matmul plan issued in this process, by shape key. Plans are chosen
# at trace time, so this is what a compiled program tiles with
_issued: dict = {}


def reset() -> None:
    """Drop the in-memory cache and the issued-plan record (tests; after
    rewriting the cache file)."""
    _state["path"] = None
    _state["entries"] = None
    _issued.clear()


def issued_plans() -> dict:
    """{shape key: {"bm", "bn", "bk", "regime", "grid_steps"}} of the
    plans `matmul_plan` has issued (a copy). `regime` is the table's
    (row_tile, skinny, prefill), or cache / measured; `grid_steps` counts
    one call's (M/bm, N/bn, K/bk) grid, per row tile for the grouped
    expert kinds."""
    return {key: dict(v) for key, v in _issued.items()}


def _issue(key: str, plan, regime: str, m: int, k: int, n: int):
    if plan is not None:
        bm, bn, bk = plan
        _issued[key] = {"bm": bm, "bn": bn, "bk": bk, "regime": regime,
                        "grid_steps": -(-m // bm) * (n // bn) * (k // bk)}
    return plan


def load_cache(path: str) -> dict:
    """Entries from a cache file. Missing file -> cold ({}); corrupt,
    unreadable, wrong-shape, or stale-template-version files log a warning
    and also return {} — the deterministic table takes over, never an
    exception on the serving path."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if not isinstance(data, dict) or not isinstance(
                data.get("entries"), dict):
            raise ValueError("expected {'version': ..., 'entries': {...}}")
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        _LOG.warning("autotune cache %s unreadable (%s); "
                     "using the deterministic table", path, e)
        return {}
    if data.get("version") != template.TEMPLATE_VERSION:
        _LOG.warning("autotune cache %s was measured against template "
                     "version %s (current %s); ignoring it", path,
                     data.get("version"), template.TEMPLATE_VERSION)
        return {}
    return data["entries"]


def save_cache(path: str, entries: dict) -> None:
    payload = {"version": template.TEMPLATE_VERSION, "entries": entries}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _entries() -> dict:
    path = debug_flags.autotune_cache_path()
    if _state["entries"] is None or _state["path"] != path:
        _state["path"] = path
        _state["entries"] = load_cache(path) if path else {}
    return _state["entries"]


def _persist(entries: dict) -> None:
    path = debug_flags.autotune_cache_path()
    if path:
        save_cache(path, entries)


# ------------------------------------------------------ entry validation

def _valid_matmul_plan(ent, *, k: int, n: int, bits: int, group_size: int):
    """A cached (bm, bn, bk) that satisfies the kernel's tiling constraints,
    or None. bm is free (ops pads the token dim to it); bn must tile N; bk
    must tile K, the byte groups, and the scale groups."""
    try:
        bm, bn, bk = int(ent["bm"]), int(ent["bn"]), int(ent["bk"])
    except (KeyError, TypeError, ValueError):
        return None
    gs = group_size if group_size != -1 else k
    vpg = pack_layout(bits)[1]
    if bm <= 0 or bn <= 0 or bk <= 0:
        return None
    if n % bn or k % bk or bk % vpg:
        return None
    if not ((gs >= bk and gs % bk == 0) or (gs < bk and bk % gs == 0)):
        return None
    return bm, bn, bk


def _valid_paged_tile(ent, page_size: int) -> Optional[int]:
    try:
        tile = int(ent["tile"])
    except (KeyError, TypeError, ValueError):
        return None
    if tile <= 0 or page_size % tile:
        return None
    return tile


# ------------------------------------------------------- measured search

def _time_candidate(fn, reps: int = 3) -> float:
    """Best-of-reps wall time of a jitted thunk. Wall-clock measurement is
    the whole point of this module and only ever runs under
    REPRO_AUTOTUNE=1 — never in CI, replay, or the sanitizer."""
    jax.block_until_ready(fn())  # compile + warm outside the timed reps
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()  # repro-lint: disable=RL001
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)  # repro-lint: disable=RL001
    return best


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _matmul_candidates(m: int, k: int, n: int, bits: int, group_size: int,
                       fallback):
    """Small deduped candidate grid around the shape: pow2 bm up to the
    m-bucket, bn/bk from the regimes both tile tables use, fallback always
    included so the search can only match or beat it."""
    gs = group_size if group_size != -1 else k
    vpg = pack_layout(bits)[1]
    mb = max(m_bucket(m), 8)
    bms = sorted({b for b in (8, 32, 128, 256) if b <= mb} | {mb})
    bns = sorted({b for b in (128, 256, 512) if b <= n and n % b == 0}
                 | {pick_block(n, 256)})
    bks = sorted({b for b in (128, 256, 512)
                  if b <= k and k % b == 0 and b % vpg == 0 and
                  ((gs >= b and gs % b == 0) or (gs < b and b % gs == 0))})
    fb_bk = pick_bk(k, gs, vpg, 256)
    if fb_bk is not None:
        bks = sorted(set(bks) | {fb_bk})
    cands = [(bm, bn, bk) for bm in bms for bn in bns for bk in bks]
    if fallback is not None and fallback not in cands:
        cands.append(fallback)
    return cands


def _search_matmul(kind: str, m: int, k: int, n: int, *, bits: int,
                   group_size: int, fallback):
    """Time every candidate on the real pallas_call with synthetic operands
    at the bucketed token count; return the fastest plan (or the fallback
    when no candidate is tileable)."""
    cands = _matmul_candidates(m, k, n, bits, group_size, fallback)
    if not cands:
        return fallback
    rng = np.random.default_rng(0)
    mb = max(m_bucket(m), 8)
    g = 1 if group_size == -1 else k // group_size
    pk = template.packed_tile_rows(k, bits)
    qw = rng.integers(0, 256, (pk, n)).astype(np.uint8)
    scale = rng.uniform(0.01, 0.1, (g, n)).astype(np.float32)
    if kind.endswith("w8a8"):
        x = rng.integers(-127, 128, (mb, k)).astype(np.int8)
    else:
        x = rng.normal(size=(mb, k)).astype(np.float32)
    kernel_fn = _MEASURE_FNS[kind]()
    best, best_t = None, float("inf")
    for bm, bn, bk in cands:
        pad = (-mb) % bm
        xp = np.pad(x, ((0, pad), (0, 0)))
        try:
            t = _time_candidate(lambda: kernel_fn(
                xp, qw, scale, bits=bits, group_size=group_size, bm=bm,
                bn=bn, bk=bk, interpret=_interpret()))
        except Exception as e:  # candidate fails to lower: skip it
            _LOG.debug("autotune candidate %s rejected: %s",
                       (bm, bn, bk), e)
            continue
        if t < best_t:
            best, best_t = (bm, bn, bk), t
    return best if best is not None else fallback


def _measure_dequant():
    from repro.kernels.dequant_matmul import dequant_matmul_pallas
    return dequant_matmul_pallas


def _measure_w8a8():
    from repro.kernels.w8a8_matmul import w8a8_matmul_pallas
    return w8a8_matmul_pallas


# the kinds the measured search times (the grouped expert kinds keep the
# table: their calls take a routing layout, not a plain token block)
_MEASURE_FNS = {
    "dequant": _measure_dequant,
    "w8a8": _measure_w8a8,
}


def _search_paged(page_size: int, kv_dtype: str, m_rows: int,
                  fallback: int) -> int:
    """Time the page-walk kernel per candidate tile on a synthetic
    two-slot case; return the fastest tile."""
    from repro.kernels.paged_attention import paged_attention_pallas

    import jax.numpy as jnp

    cands = sorted({t for t in (64, 128, 256, page_size, fallback)
                    if 0 < t <= page_size and page_size % t == 0})
    if len(cands) <= 1:
        return fallback
    rng = np.random.default_rng(0)
    s, kvh, hd, w = 2, 1, 128, 2
    rows = max(m_bucket(m_rows), 1) if m_rows > 1 else 1
    n_pages = 1 + s * w
    kf = rng.normal(size=(n_pages, page_size, kvh, hd)).astype(np.float32)
    vf = rng.normal(size=(n_pages, page_size, kvh, hd)).astype(np.float32)
    if kv_dtype == "int8":
        ks = np.abs(kf).max(axis=-1) / 127.0 + 1e-6
        vs = np.abs(vf).max(axis=-1) / 127.0 + 1e-6
        pools = (np.clip(np.round(kf / ks[..., None]), -127, 127)
                 .astype(np.int8),
                 np.clip(np.round(vf / vs[..., None]), -127, 127)
                 .astype(np.int8),
                 ks.astype(np.float32), vs.astype(np.float32))
    else:
        pools = (kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16),
                 None, None)
    bt = np.arange(1, 1 + s * w, dtype=np.int32).reshape(s, w)
    kv_len = np.full((s,), w * page_size, np.int32)
    q = rng.normal(size=(s, kvh, rows, hd)).astype(np.float32)
    best, best_t = fallback, float("inf")
    for tile in cands:
        try:
            t = _time_candidate(lambda: paged_attention_pallas(
                q, pools[0], pools[1], bt, kv_len, pools[2], pools[3],
                window=None, tile=tile, m_rows=rows if rows > 1 else 1,
                interpret=_interpret()))
        except Exception as e:
            _LOG.debug("autotune paged tile %s rejected: %s", tile, e)
            continue
        if t < best_t:
            best, best_t = tile, t
    return best


# -------------------------------------------------------- plan resolution

def matmul_plan(kind: str, m: int, k: int, n: int, *, bits: int,
                group_size: int, bm: int = 128, bn: int = 256,
                bk: int = 256):
    """(bm, bn, bk) for one quantized-matmul dispatch, or None (no valid
    tiling: the caller takes the jnp reference). kind is the shape-class
    kernel family: dequant / w8a8 / grouped_expert / grouped_w8a8 (the
    grouped kinds' m is their row tile)."""
    fallback, regime = _table_plan(kind, m, k, n, bits=bits,
                                   group_size=group_size, bm=bm, bn=bn,
                                   bk=bk)
    key = matmul_key(kind, m, k, n, bits, group_size)
    mode = debug_flags.autotune_mode()
    if mode == "0":
        return _issue(key, fallback, regime, m, k, n)
    entries = _entries()
    ent = entries.get(key)
    if ent is not None:
        plan = _valid_matmul_plan(ent, k=k, n=n, bits=bits,
                                  group_size=group_size)
        if plan is not None:
            return _issue(key, plan, "cache", m, k, n)
        _LOG.warning("autotune entry %s = %r violates the tiling "
                     "constraints; ignoring it", key, ent)
    if mode == "1" and kind in _MEASURE_FNS:
        plan = _search_matmul(kind, m, k, n, bits=bits,
                              group_size=group_size, fallback=fallback)
        if plan is not None:
            entries[key] = {"bm": plan[0], "bn": plan[1], "bk": plan[2]}
            _persist(entries)
        return _issue(key, plan, "measured", m, k, n)
    return _issue(key, fallback, regime, m, k, n)


def paged_tile(page_size: int, kv_dtype: str, m_rows: int) -> int:
    """Token tile per page-walk grid step for one paged-attention
    dispatch."""
    fallback = fallback_paged_tile(page_size)
    mode = debug_flags.autotune_mode()
    if mode == "0":
        return fallback
    key = paged_key(page_size, kv_dtype, m_rows)
    entries = _entries()
    ent = entries.get(key)
    if ent is not None:
        tile = _valid_paged_tile(ent, page_size)
        if tile is not None:
            return tile
        _LOG.warning("autotune entry %s = %r violates the tiling "
                     "constraints; ignoring it", key, ent)
    if mode == "1":
        tile = _search_paged(page_size, kv_dtype, m_rows, fallback)
        entries[key] = {"tile": tile}
        _persist(entries)
        return tile
    return fallback
