"""ChatGLM2/3 (THUDM): pre-RMSNorm decoder, grouped-query attention with
`multi_query_group_num` KV heads, rotary on the first half of each head's
channels, bias on the QKV projection only, SwiGLU MLP, untied head."""
from __future__ import annotations

from references.dense import (  # noqa: F401  (the reference contract)
    Arch, base_key, check_widths, dequant_projections, draw, global_leaves,
    groups, head_flops, logits, paged_reads, prompt_flops, token_flops)


def arch(c: dict) -> Arch:
    heads = c["num_attention_heads"]
    return Arch(
        layers=c["num_layers"], d=c["hidden_size"], heads=heads,
        kv_heads=(c["multi_query_group_num"] if c["multi_query_attention"]
                  else heads),
        head_dim=c["kv_channels"], ff=c["ffn_hidden_size"],
        vocab=c["padded_vocab_size"], norm="rms" if c["rmsnorm"] else "ln",
        eps=c["layernorm_epsilon"], act="swiglu",
        rope="half" if c["original_rope"] else "full", rope_theta=10000.0,
        qkv_bias=c["add_qkv_bias"], o_bias=c["add_bias_linear"],
        mlp_bias=c["add_bias_linear"], learned_positions=0,
        bits=c["quant"]["bits"], group_size=c["quant"]["group_size"])
