"""Plain float32 reference of a dense decoder LM, and the seeded weight law.

Nothing here imports the program under test. The benchmark draws every
weight from `--seed` with `draw`; the program is handed those weights
packed into its own format (bench/weights.py), and this reference draws
them again and computes in float32 at `highest` matmul precision:

  * quantized projections are the same round-to-nearest grid, applied to
    the same float32 draws (`fake_quant`), and used unpacked in float32;
  * every other weight (embeddings, head, norms, biases) is rounded to
    bfloat16, the dtype the configuration serves it in, then used in
    float32.

`dtype` computes the reference in a lower precision: every weight as
served is rounded to it under one scale per tensor (its largest magnitude
maps to the type's largest finite value, as a float8 weight is stored),
and the activation operand of every matmul (projection inputs and
attention q, k, v) is rounded to it as it stands. That is the control
that the output check must reject.

The layer equations follow the published descriptions of the model
families in bench/references/<model_type>.py, which map a configuration
file onto `Arch` and re-export what bench/harness.py asks of a reference
module: the width check, the weight layout and law, `logits`, and the
FLOPs and kernel shapes of a step (the yardstick of bench/flops.py).
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    """Shapes and equations of one dense decoder, as published."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    norm: str            # "rms" | "ln"
    eps: float
    act: str             # "swiglu" | "gelu_tanh"
    rope: str            # "none" | "half" (rotary on the first half of
    #                      each head's channels) | "full"
    rope_theta: float
    qkv_bias: bool
    o_bias: bool
    mlp_bias: bool
    learned_positions: int   # rows of the learned position table, or 0
    bits: int
    group_size: int


def base_key(seed: int) -> jax.Array:
    """PRNG key of a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.PRNGKey(0)
    while True:
        k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return k


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


def layer_leaves(a: Arch) -> dict[str, tuple[tuple[int, ...], str]]:
    """Path -> (shape, kind) of one block's weights, in the program's
    param-tree naming (bench/weights.py places each draw at its path)."""
    d, q, kv = a.d, a.heads * a.head_dim, a.kv_heads * a.head_dim
    out: dict = {"ln1/scale": ((d,), "norm_scale"),
                 "ln2/scale": ((d,), "norm_scale")}
    if a.norm == "ln":
        out["ln1/bias"] = ((d,), "norm_bias")
        out["ln2/bias"] = ((d,), "norm_bias")
    proj = {"attn/wq": (d, q, a.qkv_bias), "attn/wk": (d, kv, a.qkv_bias),
            "attn/wv": (d, kv, a.qkv_bias), "attn/wo": (q, d, a.o_bias),
            "mlp/wi": (d, a.ff, a.mlp_bias), "mlp/wo": (a.ff, d, a.mlp_bias)}
    if a.act == "swiglu":
        proj["mlp/wg"] = (d, a.ff, a.mlp_bias)
    for path, (k, n, bias) in proj.items():
        out[path + "/w"] = ((k, n), "linear")
        if bias:
            out[path + "/b"] = ((n,), "bias")
    return out


def groups(a: Arch) -> list[tuple[str, tuple[int, ...], dict]]:
    """The param tree's block groups: every block is one entry of the
    program's scanned stack `stack/p0`, layers 0..L-1."""
    return [("stack/p0", tuple(range(a.layers)), layer_leaves(a))]


def global_leaves(a: Arch) -> dict[str, tuple[tuple[int, ...], str]]:
    out: dict = {"embed/w": ((a.vocab, a.d), "embed"),
                 "final_norm/scale": ((a.d,), "norm_scale"),
                 "lm_head/w": ((a.d, a.vocab), "head")}
    if a.norm == "ln":
        out["final_norm/bias"] = ((a.d,), "norm_bias")
    if a.learned_positions:
        out["pos/w"] = ((a.learned_positions, a.d), "embed")
    return out


def draw(key: jax.Array, layer, path: str, shape, kind: str) -> jax.Array:
    """The float32 weight at `path` of block `layer` (-1: outside the
    blocks). Each leaf has its own key, so a leaf reads the same whether
    the whole model or one block is drawn."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           zlib.crc32(path.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind in ("linear", "head"):
        return z * (1.0 / float(np.sqrt(shape[0])))
    if kind == "norm_scale":
        return 1.0 + 0.05 * z
    if kind in ("norm_bias", "bias", "embed"):
        return 0.02 * z
    raise ValueError(f"unknown weight kind {kind!r}")


def fake_quant(w: jax.Array, bits: int, group_size: int) -> jax.Array:
    """Symmetric round-to-nearest with one scale per `group_size` rows of
    K and per output column: scale = max|w| / (2^(bits-1) - 1)."""
    k, n = w.shape
    g = k // group_size
    qmax = 2 ** (bits - 1) - 1
    wg = w.reshape(g, group_size, n)
    scale = jnp.maximum(jnp.max(jnp.abs(wg), axis=1) / qmax, 1e-10)
    q = jnp.clip(jnp.round(wg / scale[:, None, :]), -qmax, qmax)
    return (q * scale[:, None, :]).reshape(k, n)


def _cast(dtype):
    if dtype is None:
        return lambda x: x
    dt = jnp.dtype(dtype)
    return lambda x: x.astype(dt).astype(jnp.float32)


def _served(w: jax.Array, kind: str, a: Arch, dtype) -> jax.Array:
    if kind == "linear":
        w = fake_quant(w, a.bits, a.group_size)
    else:
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    if dtype is None:
        return w
    scale = jnp.max(jnp.abs(w)) / float(jnp.finfo(jnp.dtype(dtype)).max)
    scale = jnp.maximum(scale, 1e-30)
    return _cast(dtype)(w / scale) * scale


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def block_weights(a: Arch, key: jax.Array, layer, dtype=None) -> dict:
    return {p: _served(draw(key, layer, p, s, kind), kind, a, dtype)
            for p, (s, kind) in layer_leaves(a).items()}


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def global_weights(a: Arch, key: jax.Array, dtype=None) -> dict:
    return {p: _served(draw(key, -1, p, s, kind), kind, a, dtype)
            for p, (s, kind) in global_leaves(a).items()}


def _norm(a: Arch, w: dict, name: str, x: jax.Array) -> jax.Array:
    if a.norm == "rms":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + a.eps)
        return y * w[name + "/scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + a.eps) * w[name + "/scale"]
            + w[name + "/bias"])


def _lin(w: dict, name: str, x: jax.Array) -> jax.Array:
    y = jnp.einsum("...k,kn->...n", x, w[name + "/w"], precision=HI)
    if name + "/b" in w:
        y = y + w[name + "/b"]
    return y


def _rope(a: Arch, x: jax.Array, pos: jax.Array) -> jax.Array:
    """Rotate the two halves of the first `rot` channels of each head by
    angle pos * theta^(-i/half), i < half."""
    if a.rope == "none":
        return x
    rot = a.head_dim if a.rope == "full" else a.head_dim // 2
    half = rot // 2
    freq = a.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]       # (S, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def _attend(a: Arch, q, k, v):
    """Causal softmax attention of one sequence: q (S, H, hd), k and v
    (S, KVH, hd); query head h reads KV head h // (H / KVH)."""
    g = a.heads // a.kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(a.head_dim)
    n = q.shape[0]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def block_apply(a: Arch, w: dict, x: jax.Array, dtype=None) -> jax.Array:
    """One pre-norm block on x (B, S, d), positions 0..S-1."""
    cast = _cast(dtype)
    b, s, _ = x.shape
    pos = jnp.arange(s)
    h = cast(_norm(a, w, "ln1", x))
    q = _lin(w, "attn/wq", h).reshape(b, s, a.heads, a.head_dim)
    k = _lin(w, "attn/wk", h).reshape(b, s, a.kv_heads, a.head_dim)
    v = _lin(w, "attn/wv", h).reshape(b, s, a.kv_heads, a.head_dim)
    q, k = _rope(a, q, pos), _rope(a, k, pos)
    o = jax.lax.map(lambda qkv: _attend(a, *qkv),
                    (cast(q), cast(k), cast(v)))
    x = x + _lin(w, "attn/wo", cast(o.reshape(b, s, -1)))
    h = cast(_norm(a, w, "ln2", x))
    if a.act == "swiglu":
        m = jax.nn.silu(_lin(w, "mlp/wg", h)) * _lin(w, "mlp/wi", h)
    else:
        m = jax.nn.gelu(_lin(w, "mlp/wi", h), approximate=True)
    return x + _lin(w, "mlp/wo", cast(m))


@functools.partial(jax.jit, static_argnames=("a",))
def embed(a: Arch, g: dict, tokens: jax.Array) -> jax.Array:
    x = g["embed/w"][tokens]
    if a.learned_positions:
        x = x + g["pos/w"][jnp.arange(tokens.shape[1])][None]
    return x


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def head(a: Arch, g: dict, x: jax.Array, dtype=None) -> jax.Array:
    """Logits (..., V) of final hidden states x (..., d)."""
    h = _cast(dtype)(_norm(a, g, "final_norm", x))
    return jnp.einsum("...d,dv->...v", h, g["lm_head/w"], precision=HI)


def logits(a: Arch, seed: int, tokens: np.ndarray, rows: np.ndarray,
           cols: np.ndarray, dtype: Optional[str] = None) -> jax.Array:
    """Logits at the (row, col) positions of `tokens` (B, S): the
    distribution over the token that follows tokens[row, :col + 1]. The
    model is drawn block by block, so only one block's float32 weights
    are on the device at a time."""
    key = base_key(seed)
    g = global_weights(a, key, dtype=dtype)
    x = embed(a, g, jnp.asarray(tokens, jnp.int32))
    for layer in range(a.layers):
        x = block_apply(a, block_weights(a, key, layer, dtype=dtype), x,
                        dtype=dtype)
    h = x[jnp.asarray(rows), jnp.asarray(cols)]
    return head(a, g, h, dtype=dtype)


# ----------------------------------------------------------- yardstick

def projections(a: Arch) -> list[tuple[int, int]]:
    """(K, N) of every quantized projection of one block."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    mlp = [(a.d, a.ff)] * (2 if a.act == "swiglu" else 1) + [(a.ff, a.d)]
    return [(a.d, q), (a.d, kv), (a.d, kv), (q, a.d)] + mlp


def matmul_params_per_block(a: Arch) -> int:
    return sum(k * n for k, n in projections(a))


def dequant_projections(a: Arch) -> list[tuple[int, int, int]]:
    """(K, N, calls per step) of the packed dense dequant matmuls a
    forward step makes: every projection of every block, once."""
    return [(k, n, a.layers) for k, n in projections(a)]


def paged_reads(a: Arch) -> list[tuple[int, int, int, int]]:
    """(KV heads, query heads per KV head, head dim, calls per step) of
    the paged attention reads a forward step makes: one per block."""
    return [(a.kv_heads, a.heads // a.kv_heads, a.head_dim, a.layers)]


def token_flops(a: Arch, context: int) -> float:
    """Model FLOPs of one token at `context` tokens of attention context
    (itself included): two per multiply-add of every projection and of
    the output head, and 4 * head_dim per head and context token for
    the scores and the weighted values. Embedding lookups are free."""
    dense = a.layers * matmul_params_per_block(a) + a.d * a.vocab
    attn = a.layers * 4 * a.heads * a.head_dim * context
    return 2.0 * dense + attn


def prompt_flops(a: Arch, start: int, end: int) -> float:
    """Model FLOPs of the blocks on prompt positions start..end-1 (causal:
    position p attends to p + 1 tokens). The head runs on a prompt's last
    position only: add `head_flops` once per prompt."""
    n = end - start
    ctx_sum = (start + 1 + end) * n / 2.0
    dense = a.layers * matmul_params_per_block(a)
    return 2.0 * dense * n + a.layers * 4 * a.heads * a.head_dim * ctx_sum


def head_flops(a: Arch) -> float:
    return 2.0 * a.d * a.vocab
