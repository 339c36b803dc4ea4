"""Differential kernel-parity harness.

Every Pallas kernel is run in interpret mode and checked against two
independent oracles per case: the pure-jnp reference in kernels/ref.py and
a plain dequantize-then-einsum. The matrix sweeps bits x group_size x shape
— including M=1 decode rows (skinny-M tile regime), ragged K/N, and
expert-stacked weights — so new kernels and block-dispatch changes cannot
silently diverge from the packed-format math.

Runs identically under REPRO_DEQUANT_IMPL=pallas (CI's interpret-mode
lowering job) and the default ref dispatch: the ops wrappers exercised here
always lower through pallas_call(interpret=True) on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant.types import (compute_scales, dequantize, pack_layout,
                                    quantize, quantize_activation,
                                    quantize_stacked)
from repro.kernels import autotune, ops, ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.paged_harness import (build_paged_case, build_prefill_case,
                                         build_verify_case, gather_oracle,
                                         prefill_live_rows, prefill_oracle,
                                         verify_oracle)
from repro.models.attention import _quant_kv
from repro.serve.kvcache import gather_dequant_pages, gather_pages

BITS = [2, 3, 4, 8]
GROUPS = [-1, 32, 64, 128]
# (M, K, N): M=1/3 decode-skinny rows, ragged (non-pow2-tile) K/N mixes;
# M=64 decode slots at K = 128 * 7, an odd factor like ChatGLM3's
# d_ff = 128 * 107, whose row-tile plan takes the whole 896-deep K block
# in four 512-wide N blocks
DENSE_SHAPES = [(1, 128, 64), (3, 256, 80), (8, 128, 192), (64, 896, 2048)]
# (E, C, K, N): C=5 forces capacity-dim padding inside the wrapper
EXPERT_SHAPES = [(2, 5, 128, 64), (3, 8, 256, 96)]
W8A8_SHAPES = [(1, 128, 96), (7, 256, 64)]


def _key(*salts):
    return jax.random.split(jax.random.PRNGKey(sum(salts) % (2 ** 31)))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("gs", GROUPS)
@pytest.mark.parametrize("mkn", DENSE_SHAPES)
def test_dense_parity(bits, gs, mkn):
    m, k, n = mkn
    kx, kw = _key(bits, gs, m, k, n)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.1
    qt = quantize(w, bits, gs)
    autotune.reset()
    y_pal = ops.dequant_matmul(x, qt)                  # pallas interpret
    # every dense shape here fits one row tile
    assert autotune.issued_plans()[autotune.matmul_key(
        "dequant", m, k, n, bits, gs)]["regime"] == "row_tile"
    y_ref = ref.dequant_matmul_ref(x, qt.qw, qt.scale, bits=bits,
                                   group_size=gs, k=k)
    y_ein = jnp.einsum("mk,kn->mn", x.astype(jnp.bfloat16),
                       dequantize(qt, jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ein),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("gs", GROUPS)
@pytest.mark.parametrize("eckn", EXPERT_SHAPES)
def test_expert_parity(bits, gs, eckn):
    e, c, k, n = eckn
    kx, kw = _key(bits, gs, e, c, k, n)
    x = jax.random.normal(kx, (e, c, k), jnp.float32)
    w = jax.random.normal(kw, (e, k, n), jnp.float32) * 0.1
    qt = quantize_stacked(w, bits, gs)
    y_pal = ops.expert_dequant_matmul(x, qt)           # pallas interpret
    y_ref = ref.expert_dequant_matmul_ref(x, qt.qw, qt.scale, bits=bits,
                                          group_size=gs, k=k)
    y_ein = jnp.einsum("eck,ekn->ecn", x.astype(jnp.bfloat16),
                       dequantize(qt, jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ein),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("gs", GROUPS)
@pytest.mark.parametrize("mkn", W8A8_SHAPES)
def test_w8a8_parity(bits, gs, mkn):
    m, k, n = mkn
    kx, kw = _key(bits, gs, m, k, n, 7)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.1
    qt = quantize(w, bits, gs, act_bits=8)
    y_pal = ops.w8a8_matmul(x, qt)                     # pallas interpret
    xq, xs = quantize_activation(x, 8)
    y_ref = ref.w8a8_matmul_ref(xq, qt.qw, qt.scale, bits=bits,
                                group_size=gs, k=k) * xs
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # the int8-activation path must still track the float-activation
    # dequant matmul (A8 quantization noise only)
    y_f = jnp.einsum("mk,kn->mn", x, dequantize(qt, jnp.float32))
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_f),
                               rtol=5e-2, atol=5e-2 * float(jnp.max(jnp.abs(y_f))))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("gs", GROUPS)
@pytest.mark.parametrize("eckn", EXPERT_SHAPES)
def test_expert_w8a8_parity(bits, gs, eckn):
    """The expert-stacked W4A8/W8A8 kernel (per-expert int8 x int8 -> int32
    MXU dots) matches the vmapped int32 reference and tracks the
    float-activation expert dequant matmul to A8 quantization noise."""
    e, c, k, n = eckn
    kx, kw = _key(bits, gs, e, c, k, n, 7)
    x = jax.random.normal(kx, (e, c, k), jnp.float32)
    w = jax.random.normal(kw, (e, k, n), jnp.float32) * 0.1
    qt = quantize_stacked(w, bits, gs, act_bits=8)
    y_pal = ops.expert_w8a8_matmul(x, qt)              # pallas interpret
    xq, xs = quantize_activation(x.reshape(e * c, k), 8)
    y_ref = ref.expert_w8a8_matmul_ref(
        xq.reshape(e, c, k), qt.qw, qt.scale, bits=bits, group_size=gs,
        k=k) * xs.reshape(e, c, 1)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    y_f = jnp.einsum("eck,ekn->ecn", x, dequantize(qt, jnp.float32))
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_f),
                               rtol=5e-2, atol=5e-2 * float(jnp.max(jnp.abs(y_f))))


# ---------------------------------------------------------------- dispatch

def test_skinny_decode_blocks_selected():
    """M <= 8 picks the decode tile regime: bm stays at the minimal 8-row
    tile while bn/bk widen (no padding up to prefill tiles). The expert
    and int8 kinds take it; the dense dequant kind takes its row-tile plan
    instead, and reaches this regime only where no row-tile plan fits."""
    assert ops._matmul_blocks(1, 128, 256, 256) == (8, 512, 512)
    assert ops._matmul_blocks(8, 128, 256, 256) == (8, 512, 512)
    assert ops._matmul_blocks(9, 128, 256, 256) == (128, 256, 256)
    assert ops._matmul_blocks(128, 128, 256, 256) == (128, 256, 256)


def test_pick_bk_guard():
    """_pick_bk refuses un-tileable (K, group_size) combos instead of
    shrinking to bk=0 (regression: the quantize_pack loop had no guard and
    could spin into a mod-by-zero)."""
    assert ops._pick_bk(768, 3, 2, 256) is None        # gs=3 never tiles
    assert ops._pick_bk(256, 32, 2, 256) == 256
    assert ops._pick_bk(96, 64, 2, 256) is None        # 96/64 interlock
    assert ops._pick_bk(128, 128, 4, 256) == 128
    # halving must not yield a non-divisor of K (K=18 shrinks 18->9->4,
    # and 4 does not divide 18: reject, don't crash downstream)
    assert ops._pick_bk(18, 2, 4, 256) is None


def test_dequant_matmul_odd_k_falls_back():
    """K=18 / W2g2: every candidate block fails a tiling constraint — the
    wrapper must take the ref fallback, not assert inside pallas_call."""
    kx, kw = _key(13)
    x = jax.random.normal(kx, (4, 18), jnp.float32)
    w = jax.random.normal(kw, (18, 16), jnp.float32) * 0.1
    qt = quantize(w, 2, 2)
    y = ops.dequant_matmul(x, qt)
    y_ref = ref.dequant_matmul_ref(x, qt.qw, qt.scale, bits=2, group_size=2,
                                   k=18)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [2, 4])
def test_quantize_pack_adversarial_group_size(bits, monkeypatch):
    """k=768, group_size=3: no valid K tile exists — must fall back to the
    jnp reference, not crash (regression for the unguarded shrink loop)."""
    monkeypatch.setenv("REPRO_DEQUANT_IMPL", "pallas")
    w = jax.random.normal(jax.random.PRNGKey(3), (768, 16)) * 0.2
    s = compute_scales(w, bits, 3)
    packed = ops.quantize_pack(w, s, bits=bits, group_size=3)
    assert np.array_equal(np.asarray(packed),
                          np.asarray(ref.quantize_pack_ref(w, s, bits=bits)))


def test_dequant_matmul_adversarial_group_size():
    """The dense matmul wrapper takes the same graceful fallback."""
    kx, kw = _key(11)
    x = jax.random.normal(kx, (4, 768), jnp.float32)
    w = jax.random.normal(kw, (768, 16), jnp.float32) * 0.1
    qt = quantize(w, 4, 3)
    y = ops.dequant_matmul(x, qt)
    y_ref = ref.dequant_matmul_ref(x, qt.qw, qt.scale, bits=4, group_size=3,
                                   k=768)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------- paged attention

# (S, W, ps, kvh, g, hd, fills, window): M=1 single-slot decode; ragged
# per-slot kv_len with an empty slot, a page-boundary fill (== ps) and a
# full table; GQA group > 1; SWA windows that skip whole pages
PAGED_CASES = [
    (1, 2, 8, 1, 1, 32, (9,), None),
    (4, 4, 8, 2, 3, 32, (0, 1, 8, 32), None),
    (3, 4, 8, 2, 2, 16, (5, 16, 29), 7),
    (2, 6, 16, 1, 4, 64, (33, 96), 20),
]


# pool/block-table builder + gather+einsum oracle are shared with
# benchmarks/paged_attn_bench.py via kernels/paged_harness.py
def _build_paged(seed, s, w, ps, kvh, g, hd, fills, kv_bits):
    return build_paged_case(seed, s, w, ps, kvh, g, hd, fills, kv_bits)


def _gather_oracle(q, pools, bt, kv_len, window):
    return np.asarray(gather_oracle(q, pools, bt, kv_len, window), np.float32)


@pytest.mark.parametrize("kv_bits", [0, 8])
@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_attention_parity(kv_bits, case):
    s, w, ps, kvh, g, hd, fills, window = case
    q, pools, bt, kv_len = _build_paged(sum(case[:6]) + kv_bits, s, w, ps,
                                        kvh, g, hd, fills, kv_bits)
    out = np.asarray(ops.paged_attention(
        q, pools["k_pool"], pools["v_pool"], bt, kv_len,
        k_scale_pool=pools["k_scale_pool"],
        v_scale_pool=pools["v_scale_pool"], window=window))
    orc = _gather_oracle(q, pools, bt, kv_len, window)
    live = np.asarray(kv_len) > 0
    # the oracle emits garbage for empty slots (softmax over all-masked);
    # the fused kernel defines them as exact zeros
    np.testing.assert_allclose(out[live], orc[live], rtol=2e-2, atol=2e-2)
    assert np.all(out[~live] == 0.0)


@pytest.mark.parametrize("kv_bits", [0, 8])
def test_paged_attention_interpret_matches_ref_exactly(kv_bits):
    """The interpret-mode kernel is bit-comparable with the jnp page-walk
    reference (same walk order, same f32 accumulation) — exact for bf16 KV
    and for int8 KV alike on CPU."""
    s, w, ps, kvh, g, hd, fills, window = PAGED_CASES[2]
    q, pools, bt, kv_len = _build_paged(17 + kv_bits, s, w, ps, kvh, g, hd,
                                        fills, kv_bits)
    qg = q.reshape(s, kvh, g, hd)
    for win in (window, None):
        ker = paged_attention_pallas(
            qg, pools["k_pool"], pools["v_pool"], bt, kv_len,
            pools["k_scale_pool"], pools["v_scale_pool"], window=win,
            tile=ps, interpret=True)
        rr = ref.paged_attention_ref(
            qg, pools["k_pool"], pools["v_pool"], bt, kv_len,
            pools["k_scale_pool"], pools["v_scale_pool"], window=win,
            tile=ps)
        np.testing.assert_array_equal(np.asarray(ker), np.asarray(rr))


def test_paged_attention_subpage_tiles_match_whole_page():
    """Splitting oversized pages into sub-tiles (read-width regime) walks
    the same tokens: tile=ps/2 must match the gather oracle too."""
    s, w, ps, kvh, g, hd, fills, window = PAGED_CASES[1]
    q, pools, bt, kv_len = _build_paged(23, s, w, ps, kvh, g, hd, fills, 8)
    qg = q.reshape(s, kvh, g, hd)
    out = paged_attention_pallas(
        qg, pools["k_pool"], pools["v_pool"], bt, kv_len,
        pools["k_scale_pool"], pools["v_scale_pool"], window=window,
        tile=ps // 2, interpret=True).reshape(s, kvh * g, hd)
    orc = _gather_oracle(q, pools, bt, kv_len, window)
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(np.asarray(out)[live], orc[live],
                               rtol=2e-2, atol=2e-2)


def test_paged_tile_regime():
    """Common serving pages ride whole; oversized pages split to <=256."""
    assert ops._paged_tile(8) == 8
    assert ops._paged_tile(16) == 16
    assert ops._paged_tile(256) == 256
    assert ops._paged_tile(512) == 256
    assert ops._paged_tile(1024) == 256


# ------------------------------------------- spec-decode verify read (M>1)

# (S, M, W, ps, kvh, g, hd, fills, window): the small-M verify regime —
# per-slot fills must be 0 (idle) or >= M (the verify tail sits at the top
# of the fill); same empty-slot / page-boundary / GQA / SWA adversaries as
# PAGED_CASES
VERIFY_CASES = [
    (1, 2, 2, 8, 1, 1, 32, (9,), None),
    (4, 3, 4, 8, 2, 3, 32, (0, 3, 8, 32), None),
    (3, 4, 4, 8, 2, 2, 16, (5, 16, 29), 7),
    (2, 5, 6, 16, 1, 4, 64, (33, 96), 20),
]


@pytest.mark.parametrize("kv_bits", [0, 8])
@pytest.mark.parametrize("case", VERIFY_CASES)
def test_paged_attention_verify_parity(kv_bits, case):
    """The fused verify read (M query rows per slot, per-row causal fill
    mask) matches the gathered dense-attention oracle."""
    s, m, w, ps, kvh, g, hd, fills, window = case
    q, pools, bt, kv_len = build_verify_case(
        sum(case[:7]) + kv_bits, s, m, w, ps, kvh, g, hd, fills, kv_bits)
    out = np.asarray(ops.paged_attention_verify(
        q, pools["k_pool"], pools["v_pool"], bt, kv_len,
        k_scale_pool=pools["k_scale_pool"],
        v_scale_pool=pools["v_scale_pool"], window=window))
    orc = np.asarray(verify_oracle(q, pools, bt, kv_len, window), np.float32)
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(out[live], orc[live], rtol=2e-2, atol=2e-2)
    assert np.all(out[~live] == 0.0)


@pytest.mark.parametrize("kv_bits", [0, 8])
def test_paged_attention_verify_interpret_matches_ref_exactly(kv_bits):
    """Interpret-mode verify kernel is bit-comparable with the jnp
    reference page walk at M>1, like the decode read at M=1."""
    s, m, w, ps, kvh, g, hd, fills, window = VERIFY_CASES[2]
    q, pools, bt, kv_len = build_verify_case(31 + kv_bits, s, m, w, ps, kvh,
                                             g, hd, fills, kv_bits)
    qg = q.reshape(s, m, kvh, g, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(s, kvh, m * g, hd)
    for win in (window, None):
        ker = paged_attention_pallas(
            qg, pools["k_pool"], pools["v_pool"], bt, kv_len,
            pools["k_scale_pool"], pools["v_scale_pool"], window=win,
            tile=ps, m_rows=m, interpret=True)
        rr = ref.paged_attention_ref(
            qg, pools["k_pool"], pools["v_pool"], bt, kv_len,
            pools["k_scale_pool"], pools["v_scale_pool"], window=win,
            tile=ps, m_rows=m)
        np.testing.assert_array_equal(np.asarray(ker), np.asarray(rr))


@pytest.mark.parametrize("kv_bits", [0, 8])
def test_paged_attention_verify_m1_matches_decode(kv_bits):
    """A single-row verify is the decode read: same q, same pools, same
    numbers (to f32 tolerance — XLA may vectorize the two shapes
    differently) through both entry points."""
    s, w, ps, kvh, g, hd, fills, window = PAGED_CASES[2]
    q, pools, bt, kv_len = _build_paged(41 + kv_bits, s, w, ps, kvh, g, hd,
                                        fills, kv_bits)
    dec = np.asarray(ops.paged_attention(
        q, pools["k_pool"], pools["v_pool"], bt, kv_len,
        k_scale_pool=pools["k_scale_pool"],
        v_scale_pool=pools["v_scale_pool"], window=window))
    ver = np.asarray(ops.paged_attention_verify(
        q[:, None], pools["k_pool"], pools["v_pool"], bt, kv_len,
        k_scale_pool=pools["k_scale_pool"],
        v_scale_pool=pools["v_scale_pool"], window=window))[:, 0]
    np.testing.assert_allclose(ver, dec, rtol=1e-5, atol=1e-5)


# --------------------------------------- fused chunked-prefill read (M>1)

# (S, M, W, ps, kvh, g, hd, fills, chunk, window): the prefill regime —
# fill = ctx + chunk per slot, chunk <= M (left-padded bucket) and, unlike
# verify, fills may be *smaller* than M (short prompt padded into the
# bucket). Adversaries: chunk ending exactly on a page boundary, ragged
# chunk lengths inside one bucket (incl. an idle slot), SWA skipping whole
# pages behind the window, GQA group > 1
PREFILL_CASES = [
    (2, 8, 4, 8, 1, 2, 32, (8, 16), (8, 8), None),      # page boundary
    (3, 8, 4, 8, 2, 1, 32, (5, 0, 11), (5, 0, 8), None),  # ragged chunk
    (2, 8, 4, 8, 1, 2, 16, (9, 17), (8, 5), 6),        # sliding window
    (2, 4, 4, 8, 2, 4, 32, (7, 12), (4, 2), None),     # GQA g=4
]


@pytest.mark.parametrize("kv_bits", [0, 8])
@pytest.mark.parametrize("case", PREFILL_CASES)
def test_paged_attention_prefill_parity(kv_bits, case):
    """The fused prefill read (a slot's left-padded chunk against its own
    earlier pages + shared prefix pages) matches the gather-the-context
    oracle on every row the engine consumes."""
    s, m, w, ps, kvh, g, hd, fills, chunk, window = case
    q, pools, bt, kv_len = build_prefill_case(
        sum(case[:7]) + kv_bits, s, m, w, ps, kvh, g, hd, fills, kv_bits)
    out = np.asarray(ops.paged_attention_prefill(
        q, pools["k_pool"], pools["v_pool"], bt, kv_len,
        k_scale_pool=pools["k_scale_pool"],
        v_scale_pool=pools["v_scale_pool"], window=window))
    orc = np.asarray(prefill_oracle(q, pools, bt, kv_len, window, chunk),
                     np.float32)
    live = prefill_live_rows(kv_len, chunk, m)
    np.testing.assert_allclose(out[live], orc[live], rtol=2e-2, atol=2e-2)
    # idle slots read back as exact zeros (all rows dead)
    slot_live = np.asarray(kv_len) > 0
    assert np.all(out[~slot_live] == 0.0)


@pytest.mark.parametrize("kv_bits", [0, 8])
def test_paged_attention_prefill_interpret_matches_ref_exactly(kv_bits):
    """Interpret-mode prefill kernel is bit-comparable with the jnp
    reference page walk, like decode (M=1) and verify."""
    s, m, w, ps, kvh, g, hd, fills, _chunk, window = PREFILL_CASES[2]
    q, pools, bt, kv_len = build_prefill_case(53 + kv_bits, s, m, w, ps,
                                              kvh, g, hd, fills, kv_bits)
    qg = q.reshape(s, m, kvh, g, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(s, kvh, m * g, hd)
    for win in (window, None):
        ker = paged_attention_pallas(
            qg, pools["k_pool"], pools["v_pool"], bt, kv_len,
            pools["k_scale_pool"], pools["v_scale_pool"], window=win,
            tile=ps, m_rows=m, interpret=True)
        rr = ref.paged_attention_prefill_ref(
            qg, pools["k_pool"], pools["v_pool"], bt, kv_len,
            pools["k_scale_pool"], pools["v_scale_pool"], window=win,
            tile=ps, m_rows=m)
        np.testing.assert_array_equal(np.asarray(ker), np.asarray(rr))


# ------------------------------------------------- packed storage density

@pytest.mark.parametrize("bits", BITS)
def test_packed_footprint_is_subbyte(bits):
    """End-to-end storage density of the packed format: qw must cost at
    most ceil-to-group bits/8 bytes per weight — in particular W3 packs 8
    values into 3 bytes (0.375 B/value), not one byte each."""
    k, n = 256, 64
    w = jax.random.normal(jax.random.PRNGKey(bits), (k, n)) * 0.1
    qt = quantize(w, bits, 32)
    bpg, vpg = pack_layout(bits)
    assert qt.qw.dtype == jnp.uint8
    assert qt.qw.shape == (-(-k // vpg) * bpg, n)
    bytes_per_value = qt.qw.size / (k * n)
    assert bytes_per_value <= bits / 8 + 1e-9
    if bits == 3:
        assert bytes_per_value <= 0.5


# hypothesis property: quantize -> page-write -> kernel-read round trip.
# Guarded import so tier-1 collection stays green without the dev extra.
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                      # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000), s=st.integers(1, 4),
           w=st.integers(1, 4), logps=st.integers(2, 4),
           scale_mag=st.floats(0.01, 10.0))
    def test_paged_int8_roundtrip_error_bound(seed, s, w, logps, scale_mag):
        """int8 KV written through the page pool and read back (the
        single-pass gather_dequant_pages) stays within the per-(token,
        head) quantization bound scale/2 = amax/254; and the fused kernel
        reads exactly those dequantized values — its output matches the
        gather oracle over the same pool to f32 tolerance."""
        ps = 1 << logps
        kvh, g, hd = 2, 2, 16
        rng = np.random.default_rng(seed)
        fills = tuple(int(rng.integers(0, w * ps + 1)) for _ in range(s))
        q, pools, bt, kv_len = _build_paged(seed, s, w, ps, kvh, g, hd,
                                            fills, 8)
        # re-quantize a known float pool at this magnitude for the bound
        x = jnp.asarray(rng.normal(size=(1 + s * w, ps, kvh, hd)) * scale_mag,
                        jnp.float32)
        xq, xs = _quant_kv(x)
        back = gather_dequant_pages(xq, xs, bt, jnp.float32)
        orig = gather_pages(x, bt)
        bound = np.asarray(gather_pages(xs[..., None], bt))[..., 0] / 2.0
        err = np.abs(np.asarray(back) - np.asarray(orig))
        assert np.all(err <= bound[..., None] * (1 + 1e-5) + 1e-7)
        # kernel-read leg: fused output over the written pool == oracle
        out = np.asarray(ops.paged_attention(
            q, pools["k_pool"], pools["v_pool"], bt, kv_len,
            k_scale_pool=pools["k_scale_pool"],
            v_scale_pool=pools["v_scale_pool"]))
        orc = _gather_oracle(q, pools, bt, kv_len, None)
        live = np.asarray(kv_len) > 0
        np.testing.assert_allclose(out[live], orc[live], rtol=1e-4,
                                   atol=1e-4)


# ------------------------------------------------- MoE forward integration

def test_quantized_moe_forward_uses_expert_kernel(monkeypatch):
    """A quantized MoE block must route its expert matmuls through the
    expert-batched kernel and never dequantize the full expert stack."""
    from repro.configs import TINY
    from repro.models import linear as linear_mod
    from repro.models.config import MoEConfig
    from repro.models.mlp_moe import apply_moe, init_moe

    monkeypatch.setenv("REPRO_DEQUANT_IMPL", "pallas")
    cfg = TINY.replace(d_model=64, moe=MoEConfig(n_experts=4, top_k=2,
                                                 d_ff_expert=64))
    p = init_moe(cfg, jax.random.PRNGKey(0))
    for name in ("wi", "wg", "wo"):
        p["experts"][name]["w"] = quantize_stacked(
            p["experts"][name]["w"], 4, 32)

    calls = []
    real = ops.expert_dequant_matmul

    def spy(*a, **kw):
        calls.append(a[1].shape)
        return real(*a, **kw)

    monkeypatch.setattr(ops, "expert_dequant_matmul", spy)

    def no_dequant(*a, **kw):
        raise AssertionError("quantized expert stack was dequantized")

    monkeypatch.setattr(linear_mod, "dequantize", no_dequant)

    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64)) * 0.3
    y, _aux = apply_moe(cfg, p, x)
    assert y.shape == (1, 16, 64)
    assert len(calls) == 3                             # wg, wi, wo
    assert np.all(np.isfinite(np.asarray(y)))
