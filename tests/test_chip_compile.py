"""Main-path kernels compiled for a described TPU v5e chip.

Interpret mode cannot see Mosaic's tiling rules or its VMEM limits, so
every kernel below is also compiled — not run — by the TPU compiler for a
v5e chip that is described, not attached, at Llama-3.2-1B widths (d_model
2048, d_ff 8192, 32 query / 8 KV heads of 64, page 16). A block shape the
chip refuses fails here at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers must
all collect the same tests.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant.types import packed_rows
from repro.kernels import autotune
from repro.kernels.dequant_matmul import dequant_matmul_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.w8a8_matmul import w8a8_matmul_pallas

D, FF, KVH, G, HD, PAGE = 2048, 8192, 8, 4, 64, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _matmul_args(sharding, kind, bits, gs, m, k, n):
    bm, bn, bk = autotune.fallback_matmul_plan(m, k, n, kind=kind,
                                               bits=bits, group_size=gs,
                                               bm=128, bn=256, bk=256)
    g = 1 if gs == -1 else k // gs
    x_dtype = jnp.int8 if kind == "w8a8" else jnp.bfloat16
    shapes = [((max(m, bm), k), x_dtype), ((packed_rows(k, bits), n),
                                           jnp.uint8), ((g, n), jnp.float32)]
    fn = w8a8_matmul_pallas if kind == "w8a8" else dequant_matmul_pallas
    return (lambda x, qw, s: fn(x, qw, s, bits=bits, group_size=gs, bm=bm,
                                bn=bn, bk=bk, interpret=False),
            [jax.ShapeDtypeStruct(s, d, sharding=sharding)
             for s, d in shapes])


def _paged_args(sharding, m, kv_bits, slots=8, pages=129, width=16):
    pool = jnp.int8 if kv_bits == 8 else jnp.bfloat16
    shapes = [((slots, KVH, m * G, HD), jnp.float32),
              ((pages, PAGE, KVH, HD), pool), ((pages, PAGE, KVH, HD), pool),
              ((slots, width), jnp.int32), ((slots,), jnp.int32)]
    if kv_bits == 8:
        shapes += [((pages, PAGE, KVH), jnp.float32)] * 2
    tile = autotune.fallback_paged_tile(PAGE)
    return (lambda *a: paged_attention_pallas(*a, tile=tile, m_rows=m,
                                              interpret=False),
            [jax.ShapeDtypeStruct(s, d, sharding=sharding)
             for s, d in shapes])


CASES = {
    "dequant-w4-perchannel-m8": ("matmul", ("dequant", 4, -1, 8, D, FF)),
    "dequant-w4-g128-m8": ("matmul", ("dequant", 4, 128, 8, FF, D)),
    "w8a8-w4-g128-m128": ("matmul", ("w8a8", 4, 128, 128, D, FF)),
    # the row-tile plans at the benchmark cells' widest projections:
    # ChatGLM3-6B W4 down 13696 -> 4096 and gate 4096 -> 13696 (13696 =
    # 128 * 107), Granite-20B W3 up 6144 -> 24576
    "dequant-w4-g128-m64-glm-down": ("matmul",
                                     ("dequant", 4, 128, 64, 13696, 4096)),
    "dequant-w4-g128-m128-glm-gate": ("matmul",
                                      ("dequant", 4, 128, 128, 4096, 13696)),
    "dequant-w3-g128-m64-granite-up": ("matmul",
                                       ("dequant", 3, 128, 64, 6144, 24576)),
    "paged-decode-bf16": ("paged", (1, 16)),
    "paged-verify-int8": ("paged", (5, 8)),
    "paged-prefill-m16-bf16": ("paged", (16, 16)),
}


# the name each kernel's custom call carries (its `pallas_call` name): a
# profiler trace names the kernel's op events after it
KERNEL_NAMES = {"dequant": "dequant_matmul_pallas",
                "w8a8": "w8a8_matmul_pallas",
                "paged": "paged_attention_pallas"}


@pytest.mark.parametrize("case", sorted(CASES))
def test_main_path_kernel_compiles_for_v5e(one_chip, case):
    kind, params = CASES[case]
    build = _matmul_args if kind == "matmul" else _paged_args
    fn, args = build(one_chip, *params)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    name = KERNEL_NAMES[params[0] if kind == "matmul" else kind]
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(", text), name
