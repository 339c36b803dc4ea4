"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp ref oracles,
executed in interpret mode on CPU (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant.types import compute_scales, quantize
from repro.kernels import ref
from repro.kernels.channel_stats import channel_stats_pallas
from repro.kernels.dequant_matmul import dequant_matmul_pallas
from repro.kernels.quantize import quantize_pack_pallas


@pytest.mark.parametrize("bits,gs", [(2, -1), (2, 16), (3, -1), (4, -1),
                                     (4, 32), (8, -1), (8, 64)])
@pytest.mark.parametrize("mkn", [(8, 64, 32), (32, 128, 64)])
def test_dequant_matmul_vs_ref(bits, gs, mkn):
    m, k, n = mkn
    kx, kw = jax.random.split(jax.random.PRNGKey(bits * 100 + max(gs, 0)))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.1
    qt = quantize(w, bits, gs)
    y = dequant_matmul_pallas(x, qt.qw, qt.scale, bits=bits, group_size=gs,
                              bm=8, bn=32, bk=32, interpret=True)
    y_ref = ref.dequant_matmul_ref(x, qt.qw, qt.scale, bits=bits,
                                   group_size=gs, k=k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_dequant_matmul_dtypes(xdtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64)).astype(xdtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.1
    qt = quantize(w, 4, 16)
    y = dequant_matmul_pallas(x, qt.qw, qt.scale, bits=4, group_size=16,
                              bm=16, bn=32, bk=32, interpret=True)
    y_ref = ref.dequant_matmul_ref(x, qt.qw, qt.scale, bits=4, group_size=16,
                                   k=64)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("t,c,bt,bc", [(256, 128, 64, 64), (128, 64, 128, 64),
                                       (512, 32, 256, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_channel_stats_vs_ref(t, c, bt, bc, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (t, c)) * 3 + 1).astype(dtype)
    m_p, v_p = channel_stats_pallas(x, bt=bt, bc=bc, interpret=True)
    m_r, v_r = ref.channel_stats_ref(x)
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_r),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_r),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("bits,gs", [(2, -1), (2, 32), (4, -1), (4, 64),
                                     (8, -1)])
def test_quantize_pack_vs_ref(bits, gs):
    w = jax.random.normal(jax.random.PRNGKey(7), (128, 64)) * 0.2
    s = compute_scales(w, bits, gs)
    p_pal = quantize_pack_pallas(w, s, bits=bits, group_size=gs, bk=64,
                                 bn=32, interpret=True)
    p_ref = ref.quantize_pack_ref(w, s, bits=bits)
    assert np.array_equal(np.asarray(p_pal), np.asarray(p_ref))


def test_ops_wrapper_pads_tokens():
    import os

    from repro.kernels import ops
    os.environ["REPRO_DEQUANT_IMPL"] = "pallas"
    try:
        x = jax.random.normal(jax.random.PRNGKey(0), (5, 64))  # M=5 pads
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.1
        qt = quantize(w, 4, 16)
        y = ops.dequant_matmul(x, qt)
        y_ref = ref.dequant_matmul_ref(x, qt.qw, qt.scale, bits=4,
                                       group_size=16, k=64)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
    finally:
        os.environ.pop("REPRO_DEQUANT_IMPL", None)


@pytest.mark.parametrize("bits,gs", [(4, -1), (4, 32), (3, 32)])
def test_dequant_matmul_grad_matches_reference(bits, gs):
    """Norm tweaking differentiates through packed layers: the kernel
    dispatch's VJP (Pallas kernels define none) must give the gradient of
    x @ dequant(w)."""
    from repro.core.quant.types import dequantize
    from repro.kernels import ops

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    qt = quantize(jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.1,
                  bits, gs)
    ct = jax.random.normal(jax.random.PRNGKey(2), (8, 32))

    def loss(fn):
        return lambda x: jnp.sum(fn(x) * ct)

    g = jax.grad(loss(lambda x: ops.dequant_matmul(x, qt)))(x)
    g_ref = jax.grad(loss(lambda x: x @ dequantize(qt, jnp.float32)))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)
