"""GPTQ solver tests: error-compensation beats RTN under the data metric."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant.gptq import (gptq_quantize, gptq_quantize_array,
                                   hessian_from_inputs)
from repro.core.quant.types import dequantize, fake_quant


def _data_mse(w, wq, x):
    y = x @ w
    yq = x @ wq
    return float(jnp.mean((y - yq) ** 2))


@pytest.mark.parametrize("bits,gs", [(2, 16), (3, -1), (4, -1)])
def test_gptq_beats_rtn_on_data_loss(bits, gs):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (256, 64)) * jnp.linspace(0.2, 3.0, 64)  # anisotropic
    w = jax.random.normal(k2, (64, 32)) * 0.2
    h = hessian_from_inputs(x)
    qt, _ = gptq_quantize(w, h, bits=bits, group_size=gs)
    wq_gptq = dequantize(qt)
    wq_rtn = fake_quant(w, bits, gs)
    assert _data_mse(w, wq_gptq, x) < _data_mse(w, wq_rtn, x)


def test_gptq_identity_hessian_close_to_rtn():
    """With an isotropic Hessian there is nothing to compensate: GPTQ ~ RTN."""
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (32, 16)) * 0.3
    h = jnp.eye(32) * 2.0
    q, scale, _ = gptq_quantize_array(w, h, bits=8, group_size=-1, damp=1e-6)
    deq = q.astype(jnp.float32).reshape(1, 32, 16) * scale[:, None, :]
    np.testing.assert_allclose(np.asarray(deq[0]),
                               np.asarray(fake_quant(w, 8, -1)), atol=1e-4)


def test_gptq_actorder_runs_and_unpermutes():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (128, 32)) * jnp.linspace(0.1, 2.0, 32)
    w = jax.random.normal(key, (32, 16)) * 0.2
    h = hessian_from_inputs(x)
    qt, _ = gptq_quantize(w, h, bits=4, actorder=True)
    wq = dequantize(qt)
    assert wq.shape == w.shape
    # still a sane approximation after un-permutation
    assert _data_mse(w, wq, x) < _data_mse(w, jnp.zeros_like(w), x)


def test_gptq_experts_vmapped():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (4, 64, 16))
    w = jax.random.normal(key, (4, 16, 8)) * 0.2
    h = jax.vmap(hessian_from_inputs)(x)
    qt, err = gptq_quantize(w, h, bits=4)
    assert qt.qw.shape == (4, 8, 8)
    assert dequantize(qt).shape == w.shape


def test_gptq_dead_columns_survive():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (64, 16)).at[:, 3].set(0.0)  # dead input 3
    w = jax.random.normal(key, (16, 8)) * 0.2
    h = hessian_from_inputs(x)
    qt, _ = gptq_quantize(w, h, bits=4)
    assert np.all(np.isfinite(np.asarray(dequantize(qt))))


def test_gptq_factor_device_matches_in_place_and_jits():
    """Factoring on the host CPU gives the same codes as factoring where
    the Hessian lives; the in-place form stays traceable under jit."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (128, 32)) * jnp.linspace(0.1, 2.0, 32)
    w = jax.random.normal(key, (32, 16)) * 0.2
    h = hessian_from_inputs(x)
    cpu = jax.local_devices(backend="cpu")[0]
    q_host, s_host, _ = gptq_quantize_array(w, h, bits=4, group_size=16,
                                            factor_device=cpu)
    q_jit, s_jit, _ = jax.jit(lambda w, h: gptq_quantize_array(
        w, h, bits=4, group_size=16))(w, h)
    np.testing.assert_array_equal(np.asarray(q_host), np.asarray(q_jit))
    np.testing.assert_array_equal(np.asarray(s_host), np.asarray(s_jit))


def test_host_cpu_without_cpu_backend_raises(monkeypatch):
    from repro.core.quant import blockquant

    def no_cpu(backend=None):
        raise RuntimeError(f"Unknown backend {backend}")

    monkeypatch.setattr(blockquant.jax, "local_devices", no_cpu)
    with pytest.raises(RuntimeError, match="add 'cpu' to JAX_PLATFORMS"):
        blockquant.host_cpu()
