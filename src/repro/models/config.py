"""Model configuration for the unified architecture zoo.

One `ModelConfig` describes every assigned architecture: dense GQA/MQA
decoders, MLA (DeepSeek), MoE (Mixtral/DeepSeek/Jamba), Mamba2 SSD blocks,
hybrid interleaves (Jamba), encoder-decoder (Whisper), and stub-fronted
multimodal backbones (InternVL2 / Whisper audio).

The layer stack is `prefix_pattern` (unstacked, e.g. DeepSeek's first dense
layer) followed by `pattern` repeated `n_repeats` times. Repeats are stored
stacked and executed with `lax.scan`, so compile time is O(pattern), not
O(depth).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of the repeating pattern."""

    kind: str = "attn"        # "attn" | "mamba"
    mlp: str = "dense"        # "dense" | "moe" | "none"
    cross_attn: bool = False  # decoder layers of enc-dec models


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    n_shared: int = 0           # shared (always-on) experts, DeepSeek-style
    d_ff_expert: int = 0        # per-expert hidden dim
    # the shard_map EP training dispatch (models/moe_shardmap.py) drops
    # tokens past this capacity; every other path routes dropless
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    norm_topk_prob: bool = True   # renormalise the top-k gates


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: int = 0        # 0 = full-rank Q projection (V2-Lite)
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN context extension (arXiv:2309.00071) as DeepSeek-V2 publishes
    it: rotary frequencies interpolated by `factor` outside a linear ramp
    between the correction dims of `beta_fast` and `beta_slow` rotations
    over `original_max_position_embeddings`; cos/sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), and the
    attention softmax by mscale(factor, mscale_all_dim)^2."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    d_conv: int = 4
    n_groups: int = 1
    chunk: int = 64             # SSD chunk length

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    family: str = "dense"       # dense | moe | ssm | hybrid | audio | vlm
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0           # 0 = d_model // n_heads
    d_ff: int = 512
    # --- layer stack ---
    prefix_pattern: tuple[LayerSpec, ...] = ()
    pattern: tuple[LayerSpec, ...] = (LayerSpec(),)
    n_repeats: int = 2
    # --- norm / act / positions ---
    norm: str = "rmsnorm"       # rmsnorm | layernorm
    norm_eps: float = 1e-5
    act: str = "silu"           # silu (SwiGLU) | gelu (plain MLP)
    rope: str = "full"          # full | half ("2d") | none
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None
    pos_emb: str = "none"       # none | learned | sinusoidal
    max_position: int = 8192    # for learned positions
    # --- attention ---
    attention: str = "gqa"      # gqa | mla
    attn_window: Optional[int] = None  # sliding-window size (Mixtral SWA)
    qkv_bias: bool = False
    o_bias: bool = False
    mlp_bias: bool = False
    logit_softcap: float = 0.0
    # --- submodule configs ---
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[Mamba2Config] = None
    # --- enc-dec (whisper) ---
    enc_dec: bool = False
    n_enc_repeats: int = 0
    enc_pattern: tuple[LayerSpec, ...] = ()
    # --- multimodal frontend stub ---
    frontend: str = "none"      # none | audio | vision
    frontend_len: int = 0       # frames/patches prepended (vision) or enc len (audio)
    # --- embeddings / output ---
    tie_embeddings: bool = False
    # --- numerics ---
    dtype: str = "float32"          # activation/compute dtype
    param_dtype: str = "float32"
    serve_quant_bits: int = 0       # >0: serve with packed low-bit weights
    serve_quant_group: int = 128
    remat_policy: str = "nothing"   # nothing | dots (save matmul outputs)
    banded_window_attn: bool = False  # skip fully-masked SWA blocks (perf)
    chunked_decode: bool = False    # flash-style decode attention (perf)
    attn_scores_dtype: str = "float32"  # bfloat16 halves score HBM traffic
    moe_impl: str = "spmd"          # spmd | shard_map (explicit all-to-all EP)
    kv_cache_bits: int = 0          # 8: int8 KV cache (≈2x capacity/bandwidth)
    paged_attn_impl: str = "fused"  # fused: block-table-walking decode kernel
                                    # (kernels/paged_attention.py, inline int8
                                    # dequant); gather: gather->dequant->einsum
                                    # oracle path
    remat: bool = True
    attn_block_kv: int = 512        # chunked-attention kv block
    # --- distribution knobs (consumed by distributed/sharding.py) ---
    fsdp: bool = False              # shard params over the data axis too
    scan_layers: bool = True
    tp: int = 1                     # tensor-parallel width the model code is
                                    # *currently running under* (inside the
                                    # serving shard_map the engine passes a
                                    # head-localized cfg with tp>1 so
                                    # row-parallel linears psum over the
                                    # "model" axis; everywhere else tp == 1
                                    # and no collective is emitted)

    def __post_init__(self):
        # sub-configs given as plain mappings (a JSON file's overrides)
        # become their dataclasses
        for name, kind in (("moe", MoEConfig), ("mla", MLAConfig),
                           ("mamba", Mamba2Config),
                           ("rope_scaling", RopeScaling)):
            value = getattr(self, name)
            if isinstance(value, dict):
                object.__setattr__(self, name, kind(**value))

    # ------------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_layers(self) -> int:
        return len(self.prefix_pattern) + len(self.pattern) * self.n_repeats

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def all_layer_specs(self) -> list[LayerSpec]:
        return list(self.prefix_pattern) + list(self.pattern) * self.n_repeats

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def validate(self) -> None:
        assert self.n_heads % max(self.n_kv_heads, 1) == 0
        if any(s.mlp == "moe" for s in self.all_layer_specs()):
            assert self.moe is not None
        if any(s.kind == "mamba" for s in self.all_layer_specs()):
            assert self.mamba is not None
        if self.attention == "mla":
            assert self.mla is not None
        if self.enc_dec:
            assert self.n_enc_repeats > 0 and self.enc_pattern
