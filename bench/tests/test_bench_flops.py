"""FLOP and byte functions, the kernels' and the dense reference's
yardstick, against counts made by hand."""
import pytest

import bench_smoke  # noqa: F401
import flops
from references import dense
from references.dense import Arch

# 2 blocks, d 8, 2 heads of 4 with one KV head, SwiGLU 16, vocab 10
TINY = Arch(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16,
            vocab=10, norm="rms", eps=1e-5, act="swiglu", rope="half",
            rope_theta=1e4, qkv_bias=True, o_bias=False, mlp_bias=False,
            learned_positions=0, bits=4, group_size=8)


def test_projection_shapes():
    assert dense.projections(TINY) == [(8, 8), (8, 4), (8, 4), (8, 8),
                                       (8, 16), (8, 16), (16, 8)]
    # 64 + 32 + 32 + 64 + 128 + 128 + 128
    assert dense.matmul_params_per_block(TINY) == 576
    # a step calls each projection once in each of the 2 blocks
    assert dense.dequant_projections(TINY) == [
        (k, n, 2) for k, n in dense.projections(TINY)]
    # one read a block: 1 KV head serving 2 query heads of 4
    assert dense.paged_reads(TINY) == [(1, 2, 4, 2)]


def test_token_and_prompt_flops():
    # 2 * (2 blocks * 576 + head 80) + 2 blocks * 4 * 2 heads * 4 * ctx 5
    assert dense.token_flops(TINY, 5) == 2 * (1152 + 80) + 320
    # positions 3, 4: contexts 4 and 5, no head
    assert dense.prompt_flops(TINY, 3, 5) == 2 * 1152 * 2 + 64 * (4 + 5)
    assert dense.head_flops(TINY) == 160


def test_dequant_matmul_cost():
    f, b = flops.dequant_matmul_cost(8, 128, 256, 4, 128)
    assert f == 2 * 8 * 128 * 256
    # packed W4 16384 + f32 scales 256*4 + bf16 x 8*128*2 + f32 out 8*256*4
    assert b == 16384 + 1024 + 2048 + 8192
    _, b3 = flops.dequant_matmul_cost(1, 256, 128, 3, 128)
    assert b3 == 256 * 128 * 3 / 8 + 2 * 128 * 4 + 256 * 2 + 128 * 4


def test_paged_read_cost():
    f, b = flops.paged_read_cost(rows=1, live=100, kv_heads=2, group=4,
                                 head_dim=128)
    assert f == 4 * 1 * 2 * 4 * 128 * 100
    # K and V of 100 tokens in bf16, a bf16 query row in, an f32 row out
    assert b == 2 * 100 * 2 * 128 * 2 + 1 * 2 * 4 * 128 * 6


def test_least_seconds_takes_the_binding_bound():
    assert flops.least_seconds(197e12, 1.0, 197e12, 819e9) == pytest.approx(1)
    assert flops.least_seconds(1.0, 819e9, 197e12, 819e9) == pytest.approx(1)
