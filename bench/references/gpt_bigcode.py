"""GPT-BigCode (StarCoder, Granite Code): pre-LayerNorm decoder with
biases on every projection and norm, multi-query attention (one KV head)
when `multi_query`, learned absolute positions, tanh-GELU MLP."""
from __future__ import annotations

from references.dense import (  # noqa: F401  (the reference contract)
    Arch, base_key, check_widths, dequant_projections, draw, global_leaves,
    groups, head_flops, logits, paged_reads, prompt_flops, token_flops)


def arch(c: dict) -> Arch:
    heads = c["n_head"]
    if c["activation_function"] != "gelu_pytorch_tanh":
        raise ValueError(f"activation {c['activation_function']!r} is not "
                         f"the tanh GELU this reference computes")
    return Arch(
        layers=c["n_layer"], d=c["n_embd"], heads=heads,
        kv_heads=1 if c["multi_query"] else heads,
        head_dim=c["n_embd"] // heads, ff=c["n_inner"],
        vocab=c["vocab_size"], norm="ln", eps=c["layer_norm_epsilon"],
        act="gelu_tanh", rope="none", rope_theta=10000.0,
        qkv_bias=True, o_bias=True, mlp_bias=True,
        learned_positions=c["n_positions"],
        bits=c["quant"]["bits"], group_size=c["quant"]["group_size"])
