"""Hypothesis property tests on system invariants.

Hypothesis is a dev-only dependency (requirements-dev.txt); skip the whole
module when it isn't installed so tier-1 collection stays green.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.normtweak.losses import l_dist, l_kl, l_mse
from repro.core.quant.smoothquant import (fold_into_norm, scale_weight_rows,
                                          smooth_scales)
from repro.core.quant.types import (dequantize, qmax_for_bits, quantize,
                                    quantize_activation, quantize_stacked)
from repro.models.attention import _cache_write, init_kv_cache
from repro.models.config import ModelConfig


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), shift=st.floats(-2.0, 2.0))
def test_losses_nonnegative_and_monotone_in_shift(seed, shift):
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, 8, 8))
    for fn in (l_dist, l_mse, l_kl):
        v0 = float(fn(x, x))
        v1 = float(fn(x, x + shift))
        assert v0 >= -1e-6
        assert v1 >= v0 - 1e-6


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), alpha=st.floats(0.1, 0.9))
def test_smoothquant_scales_positive_and_transform_invertible(seed, alpha):
    key = jax.random.PRNGKey(seed)
    amax = jnp.abs(jax.random.normal(key, (16,))) + 0.1
    w = jax.random.normal(key, (16, 8))
    s = smooth_scales(amax, [w], alpha)
    assert bool(jnp.all(s > 0))
    w2 = scale_weight_rows(scale_weight_rows(w, s), 1.0 / s)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w), rtol=1e-5,
                               atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(bits=st.sampled_from([2, 4, 8]), seed=st.integers(0, 500))
def test_dequant_never_exceeds_group_amax(bits, seed):
    w = jax.random.normal(jax.random.PRNGKey(seed), (32, 8)) * 3.0
    qt = quantize(w, bits, 8)
    deq = np.asarray(dequantize(qt))
    wg = np.asarray(w).reshape(4, 8, 8)
    dg = deq.reshape(4, 8, 8)
    amax = np.abs(wg).max(axis=1, keepdims=True)
    assert np.all(np.abs(dg) <= amax + 1e-5)


@settings(max_examples=10, deadline=None)
@given(window=st.sampled_from([4, 8]), n=st.integers(5, 20))
def test_ring_cache_holds_last_window_positions(window, n):
    cfg = ModelConfig(d_model=16, n_heads=2, n_kv_heads=2, head_dim=8)
    cache = init_kv_cache(cfg, 1, 64, window=window)
    for t in range(n):
        k = jnp.full((1, 1, 2, 8), float(t))
        pos = jnp.full((1, 1), t, jnp.int32)
        cache = _cache_write(cache, k, k, pos)
    held = sorted(int(p) for p in np.asarray(cache["pos"][0]) if p >= 0)
    expect = list(range(max(0, n - window), n))
    assert held == expect
    # values stored where expected
    slot = (n - 1) % window
    assert float(cache["k"][0, slot, 0, 0]) == float(n - 1)


@settings(max_examples=12, deadline=None)
@given(bits=st.sampled_from([2, 4]),
       e=st.integers(1, 3),
       k=st.sampled_from([16, 32]),
       n=st.sampled_from([8, 16]),
       seed=st.integers(0, 2 ** 16))
def test_property_packed_grid_survives_expert_kernel_exactly(bits, e, k, n,
                                                             seed):
    """Random int2/int4 grids round-trip quantize_stacked -> the Pallas
    grouped expert dequant kernel bit-exactly: with every column's amax
    pinned to qmax the scale is exactly 1.0, so pack/unpack, the bf16 cast
    (integers <= 127 are exact), and the one-hot identity rows (k of them
    per expert, one 16-row tile each) add no error."""
    qmax = qmax_for_bits(bits)
    rng = np.random.default_rng(seed)
    q = rng.integers(-qmax, qmax + 1, size=(e, k, n))
    q[:, 0, :] = qmax                                  # pin scale to 1.0
    w = jnp.asarray(q, jnp.float32)
    qt = quantize_stacked(w, bits, -1)
    from repro.kernels import ops
    from repro.models.mlp_moe import expert_groups

    # expert i's group: k identity rows, padded to whole 16-row tiles
    groups, dest, _ = expert_groups(jnp.repeat(jnp.arange(e), k), e, 16)
    rows = jnp.zeros((groups.tile_expert.shape[0] * 16, k)).at[dest].set(
        jnp.tile(jnp.eye(k, dtype=jnp.float32), (e, 1)))
    deq = ops.grouped_expert_matmul(rows, qt, groups.tile_expert,
                                    groups.n_live, bm=16,
                                    out_dtype=jnp.float32)
    assert np.array_equal(np.asarray(deq)[np.asarray(dest)].reshape(e, k, n),
                          np.asarray(w))
    # the jnp unpack path agrees bit-exactly too
    assert np.array_equal(np.asarray(dequantize(qt)), np.asarray(w))


@settings(max_examples=15, deadline=None)
@given(bits=st.sampled_from([2, 3]),
       k=st.sampled_from([16, 24, 32, 64]),
       n=st.sampled_from([8, 16]),
       seed=st.integers(0, 2 ** 16))
def test_property_subbyte_pack_roundtrip_through_kernel(bits, k, n, seed):
    """Random sub-byte grids survive quantize -> bit-pack -> inline kernel
    unpack exactly: with scales pinned to 1.0 the W2/W3 word packing (4
    values/byte, 8 values per 3-byte group) and the dense dequant kernel's
    word reassembly must reproduce every code verbatim — the storage layer
    under the speculative draft. Both the Pallas path and the jnp unpack
    must agree bit-exactly with the source grid."""
    qmax = qmax_for_bits(bits)
    rng = np.random.default_rng(seed)
    q = rng.integers(-qmax, qmax + 1, size=(k, n))
    q[0, :] = qmax                                     # pin scale to 1.0
    w = jnp.asarray(q, jnp.float32)
    qt = quantize(w, bits, -1)
    # packed density: never more than bits/8 bytes per value (+ pad group)
    from repro.core.quant.types import pack_layout
    bpg, vpg = pack_layout(bits)
    assert qt.qw.shape[-2] == -(-k // vpg) * bpg
    assert np.array_equal(np.asarray(dequantize(qt)), np.asarray(q))

    from repro.kernels import ops
    deq = ops.dequant_matmul(jnp.eye(k, dtype=jnp.float32), qt,
                             out_dtype=jnp.float32)
    assert np.array_equal(np.asarray(deq), np.asarray(q))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       t=st.sampled_from([1, 3, 16]),
       k=st.sampled_from([32, 128]),
       mag=st.floats(1e-3, 1e3))
def test_property_activation_quantize_error_bounded(seed, t, k, mag):
    """int8 activation quantize-dequant error is bounded by scale/2
    elementwise: every row amax lands exactly on the grid, so rounding —
    never clipping — is the only error source (the W8A8 rescale premise)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, k)) * mag
    q, scale = quantize_activation(x, 8)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(q, np.float32) * np.asarray(scale) -
                 np.asarray(x))
    bound = np.asarray(scale) / 2 + 1e-6 * mag
    assert np.all(err <= bound)


@settings(max_examples=25, deadline=None)
@given(bits=st.sampled_from([2, 4, 8]),
       k=st.sampled_from([16, 32, 64]),
       n=st.sampled_from([8, 24]),
       seed=st.integers(0, 2 ** 16))
def test_property_quantize_bounded_and_symmetric(bits, k, n, seed):
    """Moved from test_quant_types.py so that module stays hypothesis-free."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n))
    qt = quantize(w, bits)
    deq = np.asarray(dequantize(qt))
    qmax = qmax_for_bits(bits)
    scale = np.asarray(qt.scale)[0]
    # dequantized values lie on the symmetric grid within qmax steps
    assert np.all(np.abs(deq) <= scale * qmax + 1e-6)
    # negating the input negates the quantization (symmetric grid)
    qt_neg = quantize(-w, bits)
    np.testing.assert_allclose(np.asarray(dequantize(qt_neg)), -deq, atol=1e-5)
