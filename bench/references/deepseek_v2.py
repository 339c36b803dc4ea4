"""DeepSeek-V2 (arXiv:2405.04434; DeepSeek-V2-Lite's config.json): plain
float32 reference, seeded weight law and yardstick.

Nothing here imports the program under test. Each block is pre-RMSNorm:

  * multi-head latent attention without q-LoRA, in its non-absorbed form:
    q = x Wq split per head into nope (128) and rope (64) parts; the
    latent c = RMSNorm(x Wdkv[:, :r]) and one shared rope key
    k_pe = x Wdkv[:, r:]; per head k = [c Wuk, k_pe], v = c Wuv; scores
    scaled by 1/sqrt(qk) times YaRN's mscale(factor, mscale_all_dim)^2;
  * YaRN rotary on the rope parts: inverse frequencies theta^(-2i/dim),
    divided by `factor` below the correction range of `beta_fast` and
    `beta_slow` rotations over `original_max_position_embeddings`, a
    linear ramp across it; cos and sin times mscale(f, mscale) /
    mscale(f, mscale_all_dim), with mscale(s, m) = 0.1 m ln s + 1;
  * the first `first_k_dense_replace` blocks end in a SwiGLU MLP; the
    others in a mixture of experts: softmax router, greedy top-k, the
    chosen probabilities as gates (renormalised only if `norm_topk_prob`;
    a `routed_scaling_factor` other than 1 is refused), every routed
    expert a SwiGLU of width `moe_intermediate_size`, plus
    `n_shared_experts` shared experts as one SwiGLU of n times that width. The routed experts are computed
    plainly and dropless: every expert on every token, weighted by a gate
    that is 0 where the token did not choose it.

Weights are drawn per leaf as in bench/references/dense.py (an expert
stack one expert at a time, so one expert can be drawn alone), served
projections and expert stacks on the program's round-to-nearest grid,
every other weight rounded to bfloat16; all arithmetic in float32 at
`highest` matmul precision. `dtype` gives the lower-precision control:
every weight as served rounded to it under one scale per matrix (per
expert for a stack), and every matmul's activation operand rounded to it.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from references.dense import base_key, fake_quant  # noqa: F401

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    """Shapes and equations of one DeepSeek-V2 decoder, as published."""

    layers: int
    dense_layers: int     # first_k_dense_replace
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    kv_lora: int
    ff: int               # dense MLP width
    experts: int
    top_k: int
    expert_ff: int
    shared: int
    norm_topk: bool
    vocab: int
    eps: float
    theta: float
    yarn: tuple           # (factor, original max positions, beta_fast,
    #                       beta_slow, mscale, mscale_all_dim) or ()
    bits: int
    group_size: int


def arch(c: dict) -> Arch:
    if c.get("q_lora_rank"):
        raise ValueError("this reference computes MLA without q-LoRA")
    if c["routed_scaling_factor"] != 1:
        raise ValueError("this reference scales the routed experts' sum "
                         "by 1 only")
    if (c["scoring_func"], c["topk_method"], c["hidden_act"]) != (
            "softmax", "greedy", "silu"):
        raise ValueError("the reference computes a softmax router, greedy "
                         "top-k and SwiGLU experts only")
    rs = c.get("rope_scaling")
    yarn = ()
    if rs:
        if rs["type"] != "yarn":
            raise ValueError(f"rope scaling {rs['type']!r} is not YaRN")
        yarn = (float(rs["factor"]),
                int(rs["original_max_position_embeddings"]),
                float(rs["beta_fast"]), float(rs["beta_slow"]),
                float(rs["mscale"]), float(rs["mscale_all_dim"]))
    return Arch(
        layers=c["num_hidden_layers"], dense_layers=c["first_k_dense_replace"],
        d=c["hidden_size"], heads=c["num_attention_heads"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
        v=c["v_head_dim"], kv_lora=c["kv_lora_rank"],
        ff=c["intermediate_size"], experts=c["n_routed_experts"],
        top_k=c["num_experts_per_tok"], expert_ff=c["moe_intermediate_size"],
        shared=c["n_shared_experts"], norm_topk=bool(c["norm_topk_prob"]),
        vocab=c["vocab_size"], eps=c["rms_norm_eps"],
        theta=float(c["rope_theta"]), yarn=yarn,
        bits=c["quant"]["bits"], group_size=c["quant"]["group_size"])


def check_widths(a: Arch, cfg) -> None:
    """The program's model config must run the configuration's shapes."""
    m, moe = cfg.mla, cfg.moe
    got = {"attention": cfg.attention, "d_model": cfg.d_model,
           "n_heads": cfg.n_heads, "vocab_size": cfg.vocab_size,
           "d_ff": cfg.d_ff, "norm": cfg.norm, "norm_eps": cfg.norm_eps,
           "act": cfg.act, "rope_theta": cfg.rope_theta,
           "tie_embeddings": cfg.tie_embeddings,
           "prefix": [(s.kind, s.mlp) for s in cfg.prefix_pattern],
           "pattern": [(s.kind, s.mlp) for s in cfg.pattern],
           "n_repeats": cfg.n_repeats}
    want = {"attention": "mla", "d_model": a.d, "n_heads": a.heads,
            "vocab_size": a.vocab, "d_ff": a.ff, "norm": "rmsnorm",
            "norm_eps": a.eps, "act": "silu", "rope_theta": a.theta,
            "tie_embeddings": False,
            "prefix": [("attn", "dense")] * a.dense_layers,
            "pattern": [("attn", "moe")],
            "n_repeats": a.layers - a.dense_layers}
    if m is not None:
        got["mla"] = (m.kv_lora_rank, m.q_lora_rank, m.qk_nope_head_dim,
                      m.qk_rope_head_dim, m.v_head_dim)
    want["mla"] = (a.kv_lora, 0, a.nope, a.rope, a.v)
    if moe is not None:
        got["moe"] = (moe.n_experts, moe.top_k, moe.n_shared,
                      moe.d_ff_expert, moe.norm_topk_prob)
    want["moe"] = (a.experts, a.top_k, a.shared, a.expert_ff, a.norm_topk)
    rs = cfg.rope_scaling
    got["yarn"] = () if rs is None else (
        rs.factor, rs.original_max_position_embeddings, rs.beta_fast,
        rs.beta_slow, rs.mscale, rs.mscale_all_dim)
    want["yarn"] = a.yarn
    bad = {k: (got.get(k), v) for k, v in want.items() if got.get(k) != v}
    if bad:
        raise ValueError(f"program config {cfg.name} departs from the "
                         f"configuration file (program, file): {bad}")


# ------------------------------------------------------------- weights

def _tileable(width: int) -> bool:
    """The packed kernel takes a weight width in 128-lane tiles, or whole
    where one 128-deep block of it stays within its 2**19-value weight
    block (kernels/autotune.py)."""
    return width % 128 == 0 or width * 128 <= 1 << 19


def _linear(a: Arch, k: int, n: int):
    """A projection's leaf: packed ("linear") where the group size divides
    its K and the packed kernel can tile both widths, else served in bf16
    ("dense_bf16"): DeepSeek-V2-Lite's dense MLP, of width 10944 =
    85.5 x 128."""
    packed = k % a.group_size == 0 and _tileable(k) and _tileable(n)
    return (k, n), "linear" if packed else "dense_bf16"


def _attn_leaves(a: Arch) -> dict:
    qk = a.nope + a.rope
    return {"ln1/scale": ((a.d,), "norm_scale"),
            "ln2/scale": ((a.d,), "norm_scale"),
            "attn/wq/w": _linear(a, a.d, a.heads * qk),
            "attn/wdkv/w": _linear(a, a.d, a.kv_lora + a.rope),
            "attn/kvnorm/scale": ((a.kv_lora,), "norm_scale"),
            "attn/wukv/w": _linear(a, a.kv_lora, a.heads * (a.nope + a.v)),
            "attn/wo/w": _linear(a, a.heads * a.v, a.d)}


def dense_leaves(a: Arch) -> dict:
    return dict(_attn_leaves(a), **{
        "mlp/wi/w": _linear(a, a.d, a.ff),
        "mlp/wg/w": _linear(a, a.d, a.ff),
        "mlp/wo/w": _linear(a, a.ff, a.d)})


def moe_leaves(a: Arch) -> dict:
    e, f, sh = a.experts, a.expert_ff, a.shared * a.expert_ff
    return dict(_attn_leaves(a), **{
        "moe/router/w": ((a.d, e), "router"),
        "moe/experts/wi/w": ((e, a.d, f), "experts"),
        "moe/experts/wg/w": ((e, a.d, f), "experts"),
        "moe/experts/wo/w": ((e, f, a.d), "experts"),
        "moe/shared/wi/w": _linear(a, a.d, sh),
        "moe/shared/wg/w": _linear(a, a.d, sh),
        "moe/shared/wo/w": _linear(a, sh, a.d)})


def groups(a: Arch) -> list[tuple[str, tuple[int, ...], dict]]:
    """The dense first blocks unstacked (`prefix/<i>`), then the MoE blocks
    as the program's scanned stack `stack/p0`."""
    out = [(f"prefix/{i}", (i,), dense_leaves(a))
           for i in range(a.dense_layers)]
    return out + [("stack/p0", tuple(range(a.dense_layers, a.layers)),
                   moe_leaves(a))]


def global_leaves(a: Arch) -> dict:
    return {"embed/w": ((a.vocab, a.d), "embed"),
            "final_norm/scale": ((a.d,), "norm_scale"),
            "lm_head/w": ((a.d, a.vocab), "head")}


def _leaf_key(key, layer, path):
    return jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                              zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _expert(key, layer, path, e, shape) -> jax.Array:
    """Expert e's (K, N) slice of an expert stack's draw."""
    z = jax.random.normal(jax.random.fold_in(_leaf_key(key, layer, path), e),
                          shape, jnp.float32)
    return z * (1.0 / float(np.sqrt(shape[0])))


def draw(key: jax.Array, layer, path: str, shape, kind: str) -> jax.Array:
    """The float32 weight at `path` of block `layer` (-1: outside the
    blocks): projections, router and head N(0, 1/K); norm scales
    1 + N(0, 0.05²); the embedding N(0, 0.02²); an expert stack one
    expert at a time."""
    if kind == "experts":
        return jax.vmap(lambda e: _expert(key, layer, path, e, shape[1:]))(
            jnp.arange(shape[0]))
    z = jax.random.normal(_leaf_key(key, layer, path), shape, jnp.float32)
    if kind in ("linear", "dense_bf16", "head", "router"):
        return z * (1.0 / float(np.sqrt(shape[0])))
    if kind == "norm_scale":
        return 1.0 + 0.05 * z
    if kind == "embed":
        return 0.02 * z
    raise ValueError(f"unknown weight kind {kind!r}")


def _cast(dtype):
    if dtype is None:
        return lambda x: x
    dt = jnp.dtype(dtype)
    return lambda x: x.astype(dt).astype(jnp.float32)


def _served(w: jax.Array, kind: str, a: Arch, dtype) -> jax.Array:
    if kind in ("linear", "experts"):
        w = fake_quant(w, a.bits, a.group_size)
    else:
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    if dtype is None:
        return w
    scale = jnp.max(jnp.abs(w)) / float(jnp.finfo(jnp.dtype(dtype)).max)
    scale = jnp.maximum(scale, 1e-30)
    return _cast(dtype)(w / scale) * scale


@functools.partial(jax.jit, static_argnames=("a", "moe", "dtype"))
def block_weights(a: Arch, key: jax.Array, layer, moe: bool,
                  dtype=None) -> dict:
    """One block's served weights, the routed expert stacks left out
    (`expert_weights` draws them one expert at a time)."""
    leaves = moe_leaves(a) if moe else dense_leaves(a)
    return {p: _served(draw(key, layer, p, s, kind), kind, a, dtype)
            for p, (s, kind) in leaves.items() if kind != "experts"}


def expert_weights(a: Arch, key, layer, e, dtype=None) -> dict:
    """Expert e's served (wi, wg, wo) of block `layer`."""
    return {p: _served(_expert(key, layer, p, e, s[1:]), "linear", a, dtype)
            for p, (s, kind) in moe_leaves(a).items() if kind == "experts"}


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def global_weights(a: Arch, key: jax.Array, dtype=None) -> dict:
    return {p: _served(draw(key, -1, p, s, kind), kind, a, dtype)
            for p, (s, kind) in global_leaves(a).items()}


# --------------------------------------------------------------- model

def _rms(a: Arch, x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + a.eps) \
        * scale


def _mm(x, w):
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(a: Arch, pos: jax.Array):
    """cos and sin (S, rope/2) of the YaRN rotary at positions `pos`."""
    dim, half = a.rope, a.rope // 2
    i = np.arange(half, dtype=np.float64)
    extra = a.theta ** (-2.0 * i / dim)
    mult = 1.0
    freq = extra
    if a.yarn:
        factor, max_pos, fast, slow, msc, msc_all = a.yarn

        def corr(rot):
            return dim * math.log(max_pos / (rot * 2 * math.pi)) \
                / (2 * math.log(a.theta))

        low = max(math.floor(corr(fast)), 0)
        high = min(math.ceil(corr(slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
        freq = extra / factor * ramp + extra * (1.0 - ramp)
        mult = yarn_mscale(factor, msc) / yarn_mscale(factor, msc_all)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def _rotate(x, cos, sin):
    """Rotate-half on the last axis of x (S, ..., rope); the published
    code rotates interleaved pairs, a fixed permutation of these columns."""
    half = x.shape[-1] // 2
    c = cos.reshape(cos.shape[:1] + (1,) * (x.ndim - 2) + cos.shape[1:])
    s = sin.reshape(c.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def softmax_scale(a: Arch) -> float:
    scale = 1.0 / math.sqrt(a.nope + a.rope)
    if a.yarn and a.yarn[5]:
        scale *= yarn_mscale(a.yarn[0], a.yarn[5]) ** 2
    return scale


def _mla(a: Arch, w: dict, h, cast):
    """Causal MLA of one sequence h (S, d), non-absorbed."""
    s = h.shape[0]
    cos, sin = rope_tables(a, jnp.arange(s))
    q = _mm(h, w["attn/wq/w"]).reshape(s, a.heads, a.nope + a.rope)
    q_nope, q_pe = q[..., :a.nope], _rotate(q[..., a.nope:], cos, sin)
    ckv = _mm(h, w["attn/wdkv/w"])
    c = _rms(a, ckv[:, :a.kv_lora], w["attn/kvnorm/scale"])
    k_pe = _rotate(ckv[:, a.kv_lora:], cos, sin)                # (S, rope)
    kv = _mm(cast(c), w["attn/wukv/w"]).reshape(s, a.heads, a.nope + a.v)
    k_nope, v = kv[..., :a.nope], kv[..., a.nope:]
    q_nope, q_pe, k_nope, k_pe, v = map(cast, (q_nope, q_pe, k_nope, k_pe,
                                                v))
    sc = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope, precision=HI)
          + jnp.einsum("qhr,kr->hqk", q_pe, k_pe, precision=HI))
    sc = sc * softmax_scale(a)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khv->qhv", p, v, precision=HI).reshape(s, -1)
    return _mm(cast(o), w["attn/wo/w"])


def _swiglu(w, prefix, h, cast):
    m = jax.nn.silu(_mm(h, w[prefix + "wg/w"])) * _mm(h, w[prefix + "wi/w"])
    return _mm(cast(m), w[prefix + "wo/w"])


def _gates(a: Arch, w: dict, h):
    """(T, E) routed-expert gates: the softmax probabilities of each
    token's top-k experts, 0 elsewhere."""
    probs = jax.nn.softmax(_mm(h, w["moe/router/w"]), axis=-1)
    top, idx = jax.lax.top_k(probs, a.top_k)
    if a.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


@functools.partial(jax.jit, static_argnames=("a", "moe", "dtype"))
def block_apply(a: Arch, w: dict, key, layer, x, moe: bool, dtype=None):
    """One block on x (B, S, d), positions 0..S-1. The routed experts of
    an MoE block are drawn and applied one at a time."""
    cast = _cast(dtype)
    b, s, d = x.shape
    h = cast(_rms(a, x, w["ln1/scale"]))
    x = x + jax.lax.map(lambda hh: _mla(a, w, hh, cast), h)
    h = cast(_rms(a, x, w["ln2/scale"])).reshape(b * s, d)
    if not moe:
        return x + _swiglu(w, "mlp/", h, cast).reshape(b, s, d)
    gates = _gates(a, w, h)

    def one_expert(y, e):
        we = expert_weights(a, key, layer, e, dtype)
        return y + gates[:, e, None] * _swiglu(we, "moe/experts/", h,
                                               cast), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        jnp.arange(a.experts))
    y = y + _swiglu(w, "moe/shared/", h, cast)
    return x + y.reshape(b, s, d)


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def head(a: Arch, g: dict, x: jax.Array, dtype=None) -> jax.Array:
    h = _cast(dtype)(_rms(a, x, g["final_norm/scale"]))
    return jnp.einsum("...d,dv->...v", h, g["lm_head/w"], precision=HI)


def logits(a: Arch, seed: int, tokens: np.ndarray, rows: np.ndarray,
           cols: np.ndarray, dtype: Optional[str] = None) -> jax.Array:
    """Logits at the (row, col) positions of `tokens` (B, S): the
    distribution over the token that follows tokens[row, :col + 1]. The
    model is drawn block by block, and each MoE block's routed experts
    one at a time."""
    key = base_key(seed)
    g = global_weights(a, key, dtype=dtype)
    x = g["embed/w"][jnp.asarray(tokens, jnp.int32)]
    for layer in range(a.layers):
        moe = layer >= a.dense_layers
        w = block_weights(a, key, layer, moe, dtype=dtype)
        x = block_apply(a, w, key, layer, x, moe, dtype=dtype)
    h = x[jnp.asarray(rows), jnp.asarray(cols)]
    return head(a, g, h, dtype=dtype)


# ----------------------------------------------------------- yardstick
#
# Model FLOPs count the parameters a token activates: two per multiply-add
# of every attention projection, of the dense MLP or of the router, the
# top-k routed and every shared expert, and of the head. A decoded token's
# attention is the absorbed form the program runs, at the latent width:
# scores against r + rope channels and values over r channels per head and
# context token. A prompt's attention is the non-absorbed form at the
# published head widths (qk nope + rope, v). Embedding lookups are free.

def _attn_params(a: Arch) -> int:
    qk = a.nope + a.rope
    return (a.d * a.heads * qk + a.d * (a.kv_lora + a.rope)
            + a.kv_lora * a.heads * (a.nope + a.v) + a.heads * a.v * a.d)


def active_params(a: Arch) -> int:
    """Parameters one token multiplies through, over all blocks."""
    dense = a.dense_layers * (_attn_params(a) + 3 * a.d * a.ff)
    moe_block = (_attn_params(a) + a.d * a.experts
                 + (a.top_k + a.shared) * 3 * a.d * a.expert_ff)
    return dense + (a.layers - a.dense_layers) * moe_block


def token_flops(a: Arch, context: int) -> float:
    attn = a.layers * 2 * a.heads * context * (2 * a.kv_lora + a.rope)
    return 2.0 * (active_params(a) + a.d * a.vocab) + attn


def prompt_flops(a: Arch, start: int, end: int) -> float:
    """FLOPs of the blocks on prompt positions start..end-1 (causal). The
    head runs on a prompt's last position only: add `head_flops`."""
    n = end - start
    ctx_sum = (start + 1 + end) * n / 2.0
    attn = a.layers * 2 * a.heads * (a.nope + a.rope + a.v) * ctx_sum
    return 2.0 * active_params(a) * n + attn


def head_flops(a: Arch) -> float:
    return 2.0 * a.d * a.vocab


def dequant_projections(a: Arch) -> list[tuple[int, int, int]]:
    """(K, N, calls per step) of the packed dense dequant matmuls a decode
    step makes: wq, wdkv and wo of every block, the dense blocks' MLP, the
    MoE blocks' shared experts, each where it is packed. wukv is
    dequantized whole in decode and not multiplied by that kernel; the
    routed experts run the grouped kernel (`expert_projections`)."""
    qk, sh = a.nope + a.rope, a.shared * a.expert_ff
    n_moe = a.layers - a.dense_layers
    out = [(a.d, a.heads * qk, a.layers), (a.d, a.kv_lora + a.rope, a.layers),
           (a.heads * a.v, a.d, a.layers)]
    if a.dense_layers:
        out += [(a.d, a.ff, 2 * a.dense_layers), (a.ff, a.d, a.dense_layers)]
    if a.shared:
        out += [(a.d, sh, 2 * n_moe), (sh, a.d, n_moe)]
    return [(k, n, c) for k, n, c in out if _linear(a, k, n)[1] == "linear"]


def paged_reads(a: Arch) -> list:
    """MLA reads its latent pages through a gather, not the paged
    attention kernel."""
    return []


def expert_projections(a: Arch) -> list[tuple[int, int, int]]:
    """(K, N, calls per MoE block call) of the grouped expert dequant
    matmuls: wg and wi, then wo, each streaming the experts that received
    rows (bench/metrics/expert_dequant_mm_roofline.py)."""
    return [(a.d, a.expert_ff, 2), (a.expert_ff, a.d, 1)]
