"""Operations and bytes that a call needs, computed from its shapes.

These are the yardstick of every roofline share and utilization the
benchmark reports: the least time a call could take on a chip is
max(flops / peak FLOP/s, bytes / HBM bytes/s), with the peaks from
bench/peaks.json. Bytes count each operand read once and each result
written once, as the algorithm needs them; padding, re-reads and
dead work count as the kernel's own overhead, not as work.
"""
from __future__ import annotations

from references.dense import Arch


def projections(a: Arch) -> list[tuple[int, int]]:
    """(K, N) of every quantized projection of one block."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    mlp = [(a.d, a.ff)] * (2 if a.act == "swiglu" else 1) + [(a.ff, a.d)]
    return [(a.d, q), (a.d, kv), (a.d, kv), (q, a.d)] + mlp


def matmul_params_per_block(a: Arch) -> int:
    return sum(k * n for k, n in projections(a))


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


def dequant_matmul_cost(m: int, k: int, n: int, bits: int,
                        group_size: int) -> tuple[float, float]:
    """(flops, bytes) of x (m, k) bf16 @ packed W (k, n) -> f32 (m, n):
    packed weight bits/8 bytes a value, one f32 scale per group and
    column, bf16 activations in, f32 results out."""
    groups = k // group_size if group_size > 0 else 1
    nbytes = k * n * bits / 8 + groups * n * 4 + m * k * 2 + m * n * 4
    return 2.0 * m * k * n, float(nbytes)


def paged_read_cost(rows: int, live: int, kv_heads: int, group: int,
                    head_dim: int, kv_bytes: int = 2) -> tuple[float, float]:
    """(flops, bytes) of one slot's paged attention read: `rows` query
    rows per head against `live` cached tokens of K and V. A row m of an
    M-row read sees fewer tokens; counting every row against all `live`
    tokens bounds the work from above, so the share stays a lower bound
    on the kernel's efficiency only through its bytes."""
    nbytes = (2 * live * kv_heads * head_dim * kv_bytes
              + rows * kv_heads * group * head_dim * (2 + 4))
    return 4.0 * rows * kv_heads * group * head_dim * live, float(nbytes)


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  hbm_bytes_per_s: float) -> float:
    return max(flops / peak_flops, nbytes / hbm_bytes_per_s)
