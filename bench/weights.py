"""Seeded weights, built on the device straight into the served format.

`build_served` makes the whole param tree in one jitted call: a
`lax.map` over blocks draws each block's float32 weights with the
reference's law (`references.dense.draw`), packs every projection with
the program's own round-to-nearest packer, and casts the rest to the
configuration's param dtype. Only one block's float32 weights exist at a
time, so the float model is never built. Norm tweaking changes only norm
weights, so this tree has the same structure, dtypes, shapes and
`QuantizedTensor` statics as the tree GPTQ + norm tweaking produces, and
the served path runs the same kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from references.dense import Arch, base_key, draw, global_leaves, layer_leaves


def check_widths(a: Arch, cfg) -> None:
    """The program's model config must run the configuration's shapes."""
    want = {"n_repeats": a.layers, "d_model": a.d, "n_heads": a.heads,
            "n_kv_heads": a.kv_heads, "hd": a.head_dim, "d_ff": a.ff,
            "vocab_size": a.vocab, "norm": {"rms": "rmsnorm",
                                            "ln": "layernorm"}[a.norm],
            "norm_eps": a.eps, "act": {"swiglu": "silu",
                                       "gelu_tanh": "gelu"}[a.act],
            "rope": a.rope, "qkv_bias": a.qkv_bias, "o_bias": a.o_bias,
            "mlp_bias": a.mlp_bias,
            "pos_emb": "learned" if a.learned_positions else "none"}
    if a.learned_positions:
        want["max_position"] = a.learned_positions
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad or len(cfg.pattern) != 1 or cfg.prefix_pattern:
        raise ValueError(f"program config {cfg.name} departs from the "
                         f"configuration file (program, file): {bad}")


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


@functools.partial(jax.jit, static_argnames=("a", "pdtype"))
def _build(a: Arch, key: jax.Array, pdtype: str):
    from repro.core.quant.types import compute_scales, pack, quantize_values

    leaves = layer_leaves(a)

    def one_block(layer):
        out = {}
        for path, (shape, kind) in leaves.items():
            w = draw(key, layer, path, shape, kind)
            if kind == "linear":
                s = compute_scales(w, a.bits, a.group_size)
                out[path] = (pack(quantize_values(w, s, a.bits), a.bits), s)
            else:
                out[path] = w.astype(pdtype)
        return out

    blocks = jax.lax.map(one_block, jnp.arange(a.layers))
    glob = {p: draw(key, -1, p, s, kind).astype(pdtype)
            for p, (s, kind) in global_leaves(a).items()}
    return blocks, glob


def build_served(a: Arch, cfg, seed: int) -> dict:
    """The served param tree of `cfg` (a program ModelConfig whose widths
    `check_widths` has matched to `a`), drawn from `seed`."""
    from repro.core.quant.types import QuantizedTensor

    check_widths(a, cfg)
    blocks, glob = _build(a, base_key(seed), jnp.dtype(cfg.param_dtype).name)
    leaves = layer_leaves(a)
    flat = {}
    for path, v in blocks.items():
        if leaves[path][1] == "linear":
            qw, scale = v
            flat[path] = QuantizedTensor(qw, scale, a.bits, a.group_size,
                                         leaves[path][0], 0)
        else:
            flat[path] = v
    params = _nest(glob)
    params["stack"] = {"p0": _nest(flat)}
    return params
