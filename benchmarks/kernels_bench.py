"""Kernel microbenchmarks: fused dequant-matmul vs dequant-then-matmul ref.

On CPU the Pallas kernel runs in interpret mode (not representative), so the
timed comparison is ref-vs-ref at different bit widths; the derived column
reports the *modeled* TPU v5e HBM-traffic advantage of the packed format
(weight bytes are the decode-time bottleneck for weight-only PTQ serving).

Two kernel families (see DESIGN.md "Quantized serving fast paths"):

  * dense dequant matmul          — (M, K) x packed (K, N)
  * W8A8 int8 matmul              — per-token int8 activations x packed
    weights on the int8 MXU; the model adds the 2x int8-vs-bf16 MXU rate

Plus two PR-10 rows: the autotuned tile plan vs the deterministic fallback
table on the real pallas_call (interpret mode on CPU — the *search
machinery* is what's exercised here, the win column is only meaningful on
TPU), and the fused chunked-prefill page walk vs the gather-the-context
oracle with its modeled provisioned-vs-live HBM traffic.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant.types import quantize, quantize_activation
from repro.kernels import autotune, ref
from repro.kernels.paged_harness import build_prefill_case, prefill_oracle

HBM_BW = 819e9
MXU_INT8_RATE = 2.0                    # int8 MXU throughput vs bf16 (v5e)


def _time(fn, *args, reps=5):
    fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.time() - t0) / reps


def run(rows: list):
    m, k, n = 32, 2048, 2048
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.05
    wbf = w.astype(jnp.bfloat16)

    base = jax.jit(lambda a, b: a.astype(jnp.bfloat16) @ b)
    t_fp = _time(base, x, wbf)
    rows.append((f"kernels/matmul_bf16_{m}x{k}x{n}", t_fp * 1e6,
                 f"bytes={k * n * 2}"))

    for bits, gs in [(8, -1), (4, 128), (2, 64)]:
        qt = quantize(w, bits, gs)
        fn = jax.jit(lambda xx, qw=qt.qw, sc=qt.scale: ref.dequant_matmul_ref(
            xx, qw, sc, bits=bits, group_size=gs, k=k))
        t = _time(fn, x)
        wbytes = qt.nbytes()
        # decode-time model: weight-bytes-bound; packed vs bf16 traffic
        speedup = (k * n * 2) / wbytes
        rows.append((f"kernels/dequant_matmul_w{bits}_{m}x{k}x{n}", t * 1e6,
                     f"bytes={wbytes};modeled_tpu_decode_speedup="
                     f"{speedup:.2f}x"))

    # ---- W8A8: int8 MXU path (per-token activation scales) ----
    for bits in (8, 4):
        qt8 = quantize(w, bits, -1, act_bits=8)
        xq, xs = quantize_activation(x, 8)

        def w8a8(xx_q, xx_s, qw=qt8.qw, sc=qt8.scale, b=bits):
            return ref.w8a8_matmul_ref(xx_q, qw, sc, bits=b, group_size=-1,
                                       k=k) * xx_s

        t8 = _time(jax.jit(w8a8), xq, xs)
        wbytes = qt8.nbytes()
        # two regimes, modeled separately: decode is weight-bytes-bound
        # (packed traffic advantage; the MXU rate doesn't matter there),
        # prefill is compute-bound (int8 MXU rate vs bf16)
        decode_speedup = (k * n * 2) / wbytes
        rows.append((f"kernels/w8a8_matmul_w{bits}a8_{m}x{k}x{n}", t8 * 1e6,
                     f"bytes={wbytes};modeled_tpu_decode_speedup="
                     f"{decode_speedup:.2f}x;"
                     f"modeled_tpu_prefill_mxu_speedup="
                     f"{MXU_INT8_RATE:.1f}x"))

    # ---- autotuned vs heuristic tile plan (real pallas_call, interpret) --
    ma, ka, na, bits_a, gs_a = 8, 256, 256, 4, 64
    qt_a = quantize(jax.random.normal(jax.random.PRNGKey(5),
                                      (ka, na)) * 0.05, bits_a, gs_a)
    xa = jax.random.normal(jax.random.PRNGKey(6), (ma, ka), jnp.float32)
    table = autotune.fallback_matmul_plan(ma, ka, na, kind="dequant",
                                          bits=bits_a,
                                          group_size=gs_a, bm=128, bn=256,
                                          bk=256)
    tuned = autotune._search_matmul("dequant", ma, ka, na, bits=bits_a,
                                    group_size=gs_a, fallback=table)
    kernel_fn = autotune._MEASURE_FNS["dequant"]()
    times = {}
    for tag, (bm, bn, bk) in (("table", table), ("tuned", tuned)):
        xp = jnp.pad(xa, ((0, (-ma) % bm), (0, 0)))
        times[tag] = autotune._time_candidate(lambda: kernel_fn(
            xp, qt_a.qw, qt_a.scale, bits=bits_a, group_size=gs_a, bm=bm,
            bn=bn, bk=bk, interpret=jax.default_backend() != "tpu"))
    rows.append((f"kernels/autotuned_dequant_w{bits_a}_{ma}x{ka}x{na}",
                 times["tuned"] * 1e6,
                 f"table_plan={table};tuned_plan={tuned};"
                 f"table_us={times['table'] * 1e6:.0f};"
                 f"win={times['table'] / max(times['tuned'], 1e-12):.2f}x"))

    # ---- fused chunked-prefill page walk vs gather-the-context oracle ----
    s, mrows, wtab, ps, kvh, g, hd = 2, 16, 8, 16, 2, 2, 64
    fills = (16 + mrows, 5 * ps + mrows)
    chunk = (mrows, mrows)
    from repro.kernels import ops

    q, pools, bt, kv_len = build_prefill_case(11, s, mrows, wtab, ps, kvh,
                                              g, hd, fills, 8)
    fused = jax.jit(lambda qq: ops.paged_attention_prefill(
        qq, pools["k_pool"], pools["v_pool"], bt, kv_len,
        k_scale_pool=pools["k_scale_pool"],
        v_scale_pool=pools["v_scale_pool"]))
    gathered = jax.jit(lambda qq: prefill_oracle(qq, pools, bt, kv_len,
                                                 None, chunk))
    t_fused = _time(fused, q)
    t_gather = _time(gathered, q)
    live = int(np.sum(-(-np.asarray(kv_len) // ps) * ps))
    provisioned = s * wtab * ps
    rows.append((f"kernels/prefill_attn_fused_s{s}m{mrows}ps{ps}",
                 t_fused * 1e6,
                 f"gather_us={t_gather * 1e6:.0f};"
                 f"modeled_hbm_live_vs_provisioned="
                 f"{provisioned / max(live, 1):.2f}x"))
    return rows


if __name__ == "__main__":
    out = []
    run(out)
    for r in out:
        print(",".join(str(x) for x in r))
