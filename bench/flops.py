"""Operations and bytes that a call needs, computed from its shapes.

These are the yardstick of every roofline share and utilization the
benchmark reports: the least time a call could take on a chip is
max(flops / peak FLOP/s, bytes / HBM bytes/s), with the peaks from
bench/peaks.json. Bytes count each operand read once and each result
written once, as the algorithm needs them; padding, re-reads and
dead work count as the kernel's own overhead, not as work. A model's own
FLOPs, and the kernel calls a step makes, come from the configuration's
reference module (bench/harness.py lists its yardstick).
"""
from __future__ import annotations


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
