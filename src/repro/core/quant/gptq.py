"""GPTQ (Frantar et al. 2022) in JAX: OBS-based row-serial weight
reconstruction with Hessian error compensation.

Layout: w (K, N) with out = x @ w — we quantize along K (the paper's
"columns" of the (N, K) torch layout). H = 2 Σ x xᵀ over calibration tokens.
The update loop is a `lax.fori_loop` over rows: compact HLO at any K, same
FLOP count as the blocked GPU formulation (blocking there is a locality
optimization, irrelevant under XLA fusion).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quant.types import (QuantizedTensor, compute_scales, pack,
                                    qmax_for_bits)


def hessian_from_inputs(x: jax.Array) -> jax.Array:
    """x: (..., T, K) calibration inputs for one linear -> H (K, K)."""
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    return 2.0 * x2.T @ x2


@jax.jit
def _inverse_upper_cholesky(h: jax.Array) -> jax.Array:
    """U upper-triangular with h⁻¹ = Uᵀ U (batched over leading dims),
    without forming h⁻¹: with P the index reversal and P h P = L Lᵀ,
    h⁻¹ = (P L⁻ᵀ P)(P L⁻¹ P), and P L⁻¹ P is upper-triangular."""
    k = h.shape[-1]
    l_rev = jnp.linalg.cholesky(h[..., ::-1, ::-1])
    eye = jnp.broadcast_to(jnp.eye(k, dtype=h.dtype), h.shape)
    l_inv = jax.lax.linalg.triangular_solve(l_rev, eye, left_side=True,
                                            lower=True)
    return l_inv[..., ::-1, ::-1]


def _factor(hf: jax.Array, device) -> jax.Array:
    """`_inverse_upper_cholesky` on `device` (None: where hf lives), U
    returned where hf lives."""
    if device is None:
        return _inverse_upper_cholesky(hf)
    u = _inverse_upper_cholesky(jax.device_put(hf, device))
    return jax.device_put(u, hf.sharding)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "actorder"))
def _gptq_setup(w: jax.Array, h: jax.Array, *, bits: int, group_size: int,
                damp: float, actorder: bool):
    """Everything before the factorization: dead inputs pinned, group
    scales from the original weights, act-order permutation, damping.
    Returns (dead-zeroed f32 w, scale (G, N), perm, damped permuted H)."""
    k = w.shape[0]
    wf = w.astype(jnp.float32)
    hf = h.astype(jnp.float32)

    # dead inputs: H diagonal zero -> pin to identity, zero those weight rows
    diag = jnp.diag(hf)
    dead = diag <= 0.0
    hf = hf + jnp.diag(jnp.where(dead, 1.0, 0.0))
    wf = jnp.where(dead[:, None], 0.0, wf)

    # static group scales from the original weights
    scale = compute_scales(wf, bits, group_size)                  # (G, N)

    perm = jnp.argsort(-jnp.diag(hf)) if actorder else jnp.arange(k)
    hf = hf[perm][:, perm]
    mean_diag = jnp.mean(jnp.diag(hf))
    return wf, scale, perm, hf + damp * mean_diag * jnp.eye(k)


@functools.partial(jax.jit, static_argnames=("bits",))
def _gptq_sweep(wf: jax.Array, scale: jax.Array, perm: jax.Array,
                u: jax.Array, *, bits: int):
    """Row-serial quantization in `perm` order, each row's rounding error
    propagated to the later rows through U. Returns (q int32 (K, N) in
    the original row order, mse against wf)."""
    k, n = wf.shape
    row_scale = jnp.repeat(scale, k // scale.shape[0], axis=0)    # (K, N)
    row_scale_p = row_scale[perm]
    qmax = qmax_for_bits(bits)
    rows = jnp.arange(k)

    def body(i, carry):
        wbuf, qbuf = carry
        wrow = jax.lax.dynamic_index_in_dim(wbuf, i, 0, keepdims=False)
        srow = jax.lax.dynamic_index_in_dim(row_scale_p, i, 0, keepdims=False)
        urow = jax.lax.dynamic_index_in_dim(u, i, 0, keepdims=False)   # (K,)
        d = jax.lax.dynamic_index_in_dim(urow, i, 0, keepdims=False)
        q = jnp.clip(jnp.round(wrow / srow), -qmax, qmax)
        err = (wrow - q * srow) / d
        mask = (rows > i).astype(jnp.float32)
        wbuf = wbuf - (urow * mask)[:, None] * err[None, :]
        qbuf = jax.lax.dynamic_update_index_in_dim(qbuf, q.astype(jnp.int32),
                                                   i, 0)
        return wbuf, qbuf

    _, qbuf = jax.lax.fori_loop(0, k, body,
                                (wf[perm], jnp.zeros((k, n), jnp.int32)))
    qbuf = qbuf[jnp.argsort(perm)]
    deq = qbuf.astype(jnp.float32) * row_scale
    return qbuf, jnp.mean((deq - wf) ** 2)


def gptq_quantize_array(w: jax.Array, h: jax.Array, *, bits: int,
                        group_size: int = -1, damp: float = 0.01,
                        actorder: bool = False, factor_device=None):
    """Returns (q int32 (K,N) on the symmetric grid, scale (G,N), err).
    `factor_device`: where the Hessian is factored; None keeps it with h
    (and the function traceable under jit/vmap)."""
    wf, scale, perm, hf = _gptq_setup(w, h, bits=bits, group_size=group_size,
                                      damp=damp, actorder=actorder)
    q, err = _gptq_sweep(wf, scale, perm, _factor(hf, factor_device),
                         bits=bits)
    return q, scale, err


def gptq_quantize(w: jax.Array, h: jax.Array, *, bits: int,
                  group_size: int = -1, damp: float = 0.01,
                  actorder: bool = False, act_bits: int = 0,
                  factor_device=None):
    """GPTQ for a (K, N) linear or stacked (E, K, N) experts.

    `h`: (K, K) or (E, K, K). `factor_device` as in `gptq_quantize_array`.
    Returns (QuantizedTensor, mse_err).
    """
    if w.ndim == 3:
        wf, scale, perm, hf = jax.vmap(functools.partial(
            _gptq_setup, bits=bits, group_size=group_size, damp=damp,
            actorder=actorder))(w, h)
        q, err = jax.vmap(functools.partial(_gptq_sweep, bits=bits))(
            wf, scale, perm, _factor(hf, factor_device))
        qw = jax.vmap(lambda qi: pack(qi, bits))(q)
        return QuantizedTensor(qw, scale, bits, group_size, tuple(w.shape),
                               act_bits), jnp.mean(err)
    q, scale, err = gptq_quantize_array(w, h, bits=bits, group_size=group_size,
                                        damp=damp, actorder=actorder,
                                        factor_device=factor_device)
    return QuantizedTensor(pack(q, bits), scale, bits, group_size,
                           tuple(w.shape), act_bits), err
