"""Seeded weights, built on the device straight into the served format.

`build_served` makes the whole param tree in one jitted call: for each
block group of the configuration's reference module (`groups`), a
`lax.map` over its blocks draws each block's float32 weights with the
reference's law (`draw`), packs every projection with the program's own
round-to-nearest packer (an (E, K, N) expert stack one expert at a
time), and casts the rest to the configuration's param dtype. Only one
block's float32 weights exist at a time, so the float model is never
built. Norm tweaking changes only norm weights, so this tree has the same
structure, dtypes, shapes and `QuantizedTensor` statics as the tree GPTQ
+ norm tweaking produces (a packed leaf records its block's shape, as
norm tweaking does), and the served path runs the same kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PACKED = ("linear", "experts")


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def _pack(w, bits: int, group_size: int):
    from repro.core.quant.types import compute_scales, pack, quantize_values

    s = compute_scales(w, bits, group_size)
    return pack(quantize_values(w, s, bits), bits), s


@functools.partial(jax.jit, static_argnames=("ref", "a", "pdtype"))
def _build(ref, a, key: jax.Array, pdtype: str):
    def one_block(leaves, layer):
        out = {}
        for path, (shape, kind) in leaves.items():
            w = ref.draw(key, layer, path, shape, kind)
            if kind == "linear":
                out[path] = _pack(w, a.bits, a.group_size)
            elif kind == "experts":
                out[path] = jax.lax.map(
                    lambda we: _pack(we, a.bits, a.group_size), w)
            else:
                out[path] = w.astype(pdtype)
        return out

    blocks = {}
    for gpath, layers, leaves in ref.groups(a):
        one = functools.partial(one_block, leaves)
        if gpath.startswith("stack/"):
            blocks[gpath] = jax.lax.map(one, jnp.asarray(layers))
        else:
            (layer,) = layers
            blocks[gpath] = one(layer)
    glob = {p: ref.draw(key, -1, p, s, kind).astype(pdtype)
            for p, (s, kind) in ref.global_leaves(a).items()}
    return blocks, glob


def build_served(ref, a, cfg, seed: int) -> dict:
    """The served param tree of `cfg` (a program ModelConfig whose widths
    the reference module `ref` matches to its description `a`), drawn
    from `seed`."""
    from repro.core.quant.types import QuantizedTensor

    ref.check_widths(a, cfg)
    blocks, glob = _build(ref, a, ref.base_key(seed),
                          jnp.dtype(cfg.param_dtype).name)
    flat = dict(glob)
    for gpath, _, leaves in ref.groups(a):
        for path, v in blocks[gpath].items():
            shape, kind = leaves[path]
            flat[f"{gpath}/{path}"] = (
                QuantizedTensor(*v, a.bits, a.group_size, shape, 0)
                if kind in PACKED else v)
    return _nest(flat)
