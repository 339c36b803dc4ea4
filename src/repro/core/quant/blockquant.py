"""Per-block quantization driver: walks a block's linear leaves, resolves the
calibration activations captured for each (taps), and applies RTN / GPTQ /
SmoothQuant. Routers and tiny 1-D params (conv, A_log, dt) stay float.
"""
from __future__ import annotations

from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from repro.core.quant.gptq import gptq_quantize, hessian_from_inputs
from repro.core.quant.rtn import rtn_quantize
from repro.core.quant.smoothquant import (fold_into_norm, scale_weight_rows,
                                          smooth_scales)
from repro.core.quant.types import QuantizedTensor
from repro.utils.tree import tree_get, tree_set


def iter_linears(block: dict, prefix: str = "",
                 max_ndim: int = 3) -> Iterator[tuple[str, dict]]:
    """Yield (path, linear_param_dict) for every quantizable linear.

    Per-block calibration sees (K, N) / expert (E, K, N) leaves; the deploy
    transform walks the full scan-stacked tree, where expert weights carry
    an extra layer dim (L, E, K, N), and passes max_ndim=4."""
    for k, v in block.items():
        if not isinstance(v, dict):
            continue
        w = v.get("w")
        if w is not None and not isinstance(w, dict) and \
                2 <= getattr(w, "ndim", 0) <= max_ndim:
            yield prefix + k, v
        else:
            yield from iter_linears(v, prefix + k + "/", max_ndim)


def tap_key_for(path: str) -> str:
    """Map a linear param path to its calibration-tap key."""
    if path.endswith("experts/wi") or path.endswith("experts/wg"):
        return path.rsplit("/", 1)[0]                 # .../experts
    if path.endswith("experts/wo"):
        return path.rsplit("/", 1)[0] + "_out"        # .../experts_out
    return path


# norm feeding each linear group (for SmoothQuant folding). The first matching
# prefix rule wins; linears not listed here are quantized without smoothing.
_SMOOTH_GROUPS = [
    # (norm path, [linear paths]) — resolved against the block tree
    ("ln1", ["attn/wq", "attn/wk", "attn/wv"]),
    ("ln1", ["attn/wq", "attn/wdkv"]),                # MLA
    ("ln1", ["mamba/in_proj"]),
    ("lnx", ["xattn/wq"]),
    ("ln2", ["mlp/wi", "mlp/wg"]),
    ("ln2", ["moe/shared/wi", "moe/shared/wg"]),
]


def _exists(block: dict, path: str) -> bool:
    node = block
    for k in path.split("/"):
        if not isinstance(node, dict) or k not in node:
            return False
        node = node[k]
    return True


def smooth_block(block: dict, taps: dict, alpha: float = 0.5) -> dict:
    """Fold SmoothQuant scales into norms + weights (exact float transform)."""
    for norm_path, lin_paths in _SMOOTH_GROUPS:
        lins = [p for p in lin_paths if _exists(block, p)]
        if not lins or not _exists(block, norm_path):
            continue
        x = taps.get(tap_key_for(lins[0]))
        if x is None:
            continue
        amax = jnp.max(jnp.abs(x.reshape(-1, x.shape[-1])), axis=0)
        ws = [tree_get(block, p)["w"] for p in lins]
        s = smooth_scales(amax, ws, alpha)
        block = tree_set(block, norm_path, fold_into_norm(
            tree_get(block, norm_path), s))
        for p in lins:
            lin = dict(tree_get(block, p))
            lin["w"] = scale_weight_rows(lin["w"], s)
            block = tree_set(block, p, lin)
        # keep routing decisions identical: compensate the router to see
        # the un-smoothed activations (router stays float)
        if norm_path == "ln2" and _exists(block, "moe/router"):
            router = dict(tree_get(block, "moe/router"))
            router["w"] = scale_weight_rows(router["w"], s)
            block = tree_set(block, "moe/router", router)
        # routed experts share the ln2 input: scale their rows too
        if norm_path == "ln2" and _exists(block, "moe/experts"):
            for nm in ("wi", "wg"):
                lin = dict(tree_get(block, f"moe/experts/{nm}"))
                lin["w"] = (lin["w"].astype(jnp.float32) *
                            s[None, :, None]).astype(lin["w"].dtype)
                block = tree_set(block, f"moe/experts/{nm}", lin)
    return block


def awq_block(block: dict, taps: dict, *, bits: int,
              group_size: int = -1) -> dict:
    """AWQ: grid-searched activation-aware scales, folded like SmoothQuant."""
    from repro.core.quant.awq import awq_search_scales

    for norm_path, lin_paths in _SMOOTH_GROUPS:
        lins = [p for p in lin_paths if _exists(block, p)]
        if not lins or not _exists(block, norm_path):
            continue
        x = taps.get(tap_key_for(lins[0]))
        if x is None:
            continue
        ws = [tree_get(block, p)["w"] for p in lins]
        s, _ = awq_search_scales(x, ws, bits=bits, group_size=group_size)
        block = tree_set(block, norm_path, fold_into_norm(
            tree_get(block, norm_path), s))
        for p in lins:
            lin = dict(tree_get(block, p))
            lin["w"] = scale_weight_rows(lin["w"], s)
            block = tree_set(block, p, lin)
        if norm_path == "ln2" and _exists(block, "moe/router"):
            router = dict(tree_get(block, "moe/router"))
            router["w"] = scale_weight_rows(router["w"], s)
            block = tree_set(block, "moe/router", router)
        if norm_path == "ln2" and _exists(block, "moe/experts"):
            for nm in ("wi", "wg"):
                lin = dict(tree_get(block, f"moe/experts/{nm}"))
                lin["w"] = (lin["w"].astype(jnp.float32) *
                            s[None, :, None]).astype(lin["w"].dtype)
                block = tree_set(block, f"moe/experts/{nm}", lin)
    return block


def host_cpu() -> jax.Device:
    """The host CPU device. GPTQ factors its K x K Hessians there: the
    dense Cholesky and triangular solve need several K x K buffers of
    scratch, which on an accelerator compete with the model being
    calibrated for device memory, and on the host only with host memory."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "GPTQ factors its Hessians on the host CPU backend, which this "
            "JAX process does not have: add 'cpu' to JAX_PLATFORMS") from e


def quantize_block(block: dict, taps: Optional[dict], *, method: str = "gptq",
                   bits: int = 4, group_size: int = -1, act_bits: int = 0,
                   alpha: float = 0.5, damp: float = 0.01,
                   actorder: bool = False,
                   skip_substrings: tuple = ("router",)) -> dict:
    """Quantize every linear in the block. Returns a new block tree."""
    if method == "smoothquant":
        assert taps is not None, "SmoothQuant needs calibration taps"
        block = smooth_block(block, taps, alpha)
    elif method == "awq":
        assert taps is not None, "AWQ needs calibration taps"
        block = awq_block(block, taps, bits=bits, group_size=group_size)

    for path, lin in list(iter_linears(block)):
        if any(s in path for s in skip_substrings):
            continue
        w = lin["w"]
        if isinstance(w, QuantizedTensor):
            continue
        if method == "gptq":
            assert taps is not None, "GPTQ needs calibration taps"
            x = taps[tap_key_for(path)]
            if w.ndim == 3:  # experts: per-expert Hessian from (E, C, K)
                h = jax.vmap(hessian_from_inputs)(x)
            else:
                h = hessian_from_inputs(x)
            qt, _ = gptq_quantize(w, h, bits=bits, group_size=group_size,
                                  damp=damp, actorder=actorder,
                                  act_bits=act_bits, factor_device=host_cpu())
        else:  # rtn | smoothquant (weights via RTN after folding)
            qt = rtn_quantize(w, bits=bits, group_size=group_size,
                              act_bits=act_bits)
        new_lin = dict(lin)
        new_lin["w"] = qt
        block = tree_set(block, path, new_lin)
    return block
