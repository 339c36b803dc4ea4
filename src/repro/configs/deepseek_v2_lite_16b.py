"""DeepSeek-V2-Lite (16B) [arXiv:2405.04434]: 27L d2048 16H, MLA
(kv_lora 512, nope 128 + rope 64, v 128) with YaRN RoPE (factor 40 over
4096 positions, mscale = mscale_all_dim = 0.707), MoE 64 routed top-6 +
2 shared experts (d_ff_expert 1408) behind a softmax router whose top-6
gates are not renormalised, first layer dense (d_ff 10944), RMSNorm eps
1e-6, vocab 102400 (huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
config.json).

Assignment-spec note: the pool line says "2 shared+160 routed"; 160 routed is
V2-*large*. We follow the "64e top-6" clause (matches the Lite paper).
"""
from repro.models.config import (LayerSpec, MLAConfig, ModelConfig,
                                 MoEConfig, RopeScaling)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    vocab_size=102400,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,  # first dense layer
    prefix_pattern=(LayerSpec(kind="attn", mlp="dense"),),
    pattern=(LayerSpec(kind="attn", mlp="moe"),),
    n_repeats=26,
    norm="rmsnorm",
    norm_eps=1e-6,
    act="silu",
    rope="full",
    rope_scaling=RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    attention="mla",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff_expert=1408,
                  norm_topk_prob=False),
    serve_quant_bits=4,
    fsdp=True,  # 16B: replicated fp32 params+Adam exceed v5e HBM (see §Perf)
    moe_impl="shard_map",  # explicit all-to-all EP dispatch (§Perf: -88% coll.)
)

SMOKE = CONFIG.replace(
    vocab_size=512, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
    d_ff=160, n_repeats=2,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    moe=MoEConfig(n_experts=8, top_k=2, n_shared=1, d_ff_expert=32,
                  norm_topk_prob=False))
