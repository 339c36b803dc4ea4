#!/usr/bin/env python3
"""Smoke run of the norm-tweak W4 quantize-then-serve path on a TPU.

    python chip_smoke.py          # one chip: kernel oracles, then serving
    python chip_smoke.py --tp 4   # four chips: TP=4 serving against TP=1

Default run, on `jax.devices()[:1]` with no mesh, at the published widths
of Llama-3.2-1B (random weights from a seed):

  A. every main-path Pallas kernel instance at the model's real shapes
     against its `kernels/ref.py` oracle — dequant matmul at W4
     per-channel, W4/W3/W2 g128; W8A8 at W4/W8, per-channel and g128; the
     paged decode (m=1), verify (m=5) and chunked-prefill (m=16) reads on
     bf16 and int8 KV at the model's KVH and at its TP=4 shard (KVH/4);
  B. the launcher's own path (`repro.launch.serve`): random init ->
     self-generated calibration -> GPTQ + norm tweaking to packed W4 g128
     -> the continuous engine serving a few greedy requests through the
     compiled dequant and fused paged-attention kernels.

`--tp 4` runs only the serve phase: one RTN W4 g128 packing served at
TP=4 on `jax.devices()[:4]`, its prefill logits compared with TP=1 on the
first of them, and the per-device bytes of the TP placement.

It fails (non-zero, no result line) without a TPU, outside a checkout,
under REPRO_DEQUANT_IMPL, or when any check fails. The last stdout line
of a passing run is the JSON result. Times printed are one smoke run, not
a benchmark. The compile cache lives in $JAX_COMPILATION_CACHE_DIR when
set, else in `.jax_cache/` of the checkout.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
T0 = time.perf_counter()

# normalised error bounds, max|kernel - oracle| / max|oracle|. Both sides
# of a matmul multiply the same bf16 (or int8) operands exactly and
# accumulate in f32, so only the summation order differs (~1e-6); the
# paged read adds f32 MXU dots whose pass count may differ from the
# oracle's. A wrong unpack, scale group, page or head is O(1e-1) or more.
MATMUL_BOUND = 1e-3
PAGED_BOUND = 1e-2
# TP=4 against TP=1 prefill logits: the row-parallel psum reorders f32
# sums and TPU f32 matmuls round operands to bf16 (2**-8 ~ 4e-3)
TP_LOGIT_BOUND = 2e-2

SERVE_FLAGS = [  # one prompt bucket and one decode depth: few compiles
    "--no-smoke", "--requests", "8", "--rate", "0", "--slots", "8",
    "--prompt-len-min", "17", "--prompt-len-max", "32",
    "--prefill-bucket", "32", "--max-new-min", "17", "--max-new-max", "17",
    "--page-size", "16", "--paged-attn", "fused", "--seed", "0",
]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spent in backend compiles (or persistent-cache reads,
    which replace them), summed from its own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0

        def listen(name, secs, **_):
            if name == self.EVENT:
                self.seconds += secs
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def use_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(HERE, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# ------------------------------------------------------------ phase A

def projection_shapes(cfg) -> list[tuple[int, int]]:
    """(K, N) of every attention/MLP projection of a dense decoder."""
    d, hd = cfg.d_model, cfg.head_dim
    return sorted({(d, cfg.n_heads * hd), (d, cfg.n_kv_heads * hd),
                   (d, cfg.d_ff), (cfg.d_ff, d), (cfg.n_heads * hd, d)})


def _rel_err(y, y_ref) -> float:
    import numpy as np

    y = np.asarray(y, np.float32)
    y_ref = np.asarray(y_ref, np.float32)
    if not (np.isfinite(y).all() and np.isfinite(y_ref).all()):
        return float("inf")
    return float(np.max(np.abs(y - y_ref)) / max(np.max(np.abs(y_ref)),
                                                 1e-30))


def matmul_cases(cfg):
    """(kind, bits, group_size, k, n) of the main-path matmul instances,
    each run at M=8 and M=128: dequant on every projection shape, W8A8 on
    the MLP's."""
    shapes = projection_shapes(cfg)
    mlp = [s for s in shapes if cfg.d_ff in s]
    for bits, gs in ((4, -1), (4, 128), (3, 128), (2, 128)):
        for k, n in shapes:
            yield "dequant", bits, gs, k, n
    for bits, gs in ((4, -1), (4, 128), (8, -1), (8, 128)):
        for k, n in mlp:
            yield "w8a8", bits, gs, k, n


def paged_cases(cfg):
    """(kvh, m_rows, kv_bits) of the paged reads: decode, verify and
    chunked prefill, bf16 and int8 KV, whole model and one TP=4 shard."""
    for kvh in sorted({cfg.n_kv_heads, max(1, cfg.n_kv_heads // 4)},
                      reverse=True):
        for m in (1, 5, 16):
            for kv_bits in (16, 8):
                yield kvh, m, kv_bits


@functools.cache
def _normal(shape, seed):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key: jax.random.normal(key, shape, jnp.float32))(
        jax.random.PRNGKey(seed))


def run_matmul_case(kind, bits, gs, k, n) -> dict:
    """Errors at M=8 and M=128. Data, packing, kernel and oracle compile
    as separate programs: fused into one, XLA takes several times longer
    over them than over the four apart."""
    import jax
    import jax.numpy as jnp

    from repro.core.quant.types import (QuantizedTensor, quantize,
                                        quantize_activation)
    from repro.kernels import ops, ref

    x_all = _normal((128, k), k)
    qt = jax.jit(quantize, static_argnums=(1, 2))(
        _normal((k, n), k + n) * 0.02, bits, gs)
    if kind == "dequant":
        kernel = jax.jit(lambda x, qt: ops.dequant_matmul(
            x, qt, out_dtype=jnp.float32))
        oracle = jax.jit(lambda x, qt: ref.dequant_matmul_ref(
            x, qt.qw, qt.scale, bits=bits, group_size=gs, k=k))
    else:
        qt = QuantizedTensor(qt.qw, qt.scale, bits, gs, (k, n), 8)
        kernel = jax.jit(lambda x, qt: ops.w8a8_matmul(
            x, qt, out_dtype=jnp.float32))

        @jax.jit
        def oracle(x, qt):
            xq, xs = quantize_activation(x, 8)
            return ref.w8a8_matmul_ref(xq, qt.qw, qt.scale, bits=bits,
                                       group_size=gs, k=k) * xs

    return {m: _rel_err(kernel(x_all[:m], qt), oracle(x_all[:m], qt))
            for m in (8, 128)}


def run_paged_case(cfg, kvh, m, kv_bits, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import autotune, ops, ref
    from repro.kernels.paged_harness import build_prefill_case

    s, w, ps = 8, 16, 16
    g = cfg.n_heads // cfg.n_kv_heads
    rng = np.random.default_rng(seed)
    # fills from empty to the full table, verify/prefill rows included
    fills = rng.integers(m, w * ps + 1, size=s)
    fills[0] = 0
    q, pools, bt, kv_len = build_prefill_case(
        seed, s, m, w, ps, kvh, g, cfg.head_dim, fills, kv_bits)
    read = {1: ops.paged_attention, 5: ops.paged_attention_verify}.get(
        m, ops.paged_attention_prefill)
    tile = autotune.paged_tile(ps, "int8" if kv_bits == 8 else "bf16", m)

    @jax.jit
    def case(q, pools, bt, kv_len):
        y = read(q if m > 1 else q[:, 0], pools["k_pool"], pools["v_pool"],
                 bt, kv_len, k_scale_pool=pools["k_scale_pool"],
                 v_scale_pool=pools["v_scale_pool"], out_dtype=jnp.float32)
        # the oracle on the kernel's own (S, KVH, m*G, hd) m-major rows
        qg = q.reshape(s, m, kvh, g, -1).transpose(0, 2, 1, 3, 4)
        with jax.default_matmul_precision("highest"):
            o = ref.paged_attention_ref(
                qg.reshape(s, kvh, m * g, -1), pools["k_pool"],
                pools["v_pool"], bt, kv_len, pools["k_scale_pool"],
                pools["v_scale_pool"], tile=tile, m_rows=m)
        o = o.reshape(s, kvh, m, g, -1).transpose(0, 2, 1, 3, 4)
        return y, o.reshape(y.shape)

    return _rel_err(*case(q, pools, bt, kv_len))


def phase_kernels(cfg) -> None:
    say(f"phase A: kernel instances against kernels/ref.py at "
        f"{cfg.name} shapes (bound {MATMUL_BOUND:g} matmul, "
        f"{PAGED_BOUND:g} paged; error = max|kernel-oracle|/max|oracle|)")
    bad = []
    for kind, bits, gs, k, n in matmul_cases(cfg):
        for m, err in run_matmul_case(kind, bits, gs, k, n).items():
            name = f"{kind} W{bits} g{gs} M={m} {k}->{n}"
            ok = err <= MATMUL_BOUND
            say(f"  {'ok ' if ok else 'BAD'} {name:34s} err {err:.3e}")
            if not ok:
                bad.append(name)
    seed = 0
    for kvh, m, kv_bits in paged_cases(cfg):
        seed += 1
        err = run_paged_case(cfg, kvh, m, kv_bits, seed)
        read = {1: "decode"}.get(m, "verify" if m < 16 else "prefill")
        name = (f"paged {read} m={m} {'int8' if kv_bits == 8 else 'bf16'} "
                f"KV KVH={kvh}")
        ok = err <= PAGED_BOUND
        say(f"  {'ok ' if ok else 'BAD'} {name:34s} err {err:.3e}")
        if not ok:
            bad.append(name)
    _normal.cache_clear()  # hand the device memory back to phase B
    if bad:
        fail(f"{len(bad)} kernel instance(s) outside their bound: {bad}")


# ------------------------------------------------------------ phase B

def describe(cfg) -> str:
    return (f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.n_heads} heads ({cfg.n_kv_heads} KV), head_dim "
            f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")


def quantized_leaves(params):
    import jax

    from repro.core.quant.types import QuantizedTensor

    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    return [x for x in leaves if isinstance(x, QuantizedTensor)]


def check_tiled(qts) -> None:
    """No projection may take the untileable-K jnp fallback of ops."""
    from repro.kernels import autotune

    for qt in qts:
        for m in (8, 128):
            kind = "w8a8" if qt.act_bits == 8 else "dequant"
            if autotune.matmul_plan(kind, m, qt.k, qt.n, bits=qt.bits,
                                    group_size=qt.group_size) is None:
                fail(f"{kind} K={qt.k} N={qt.n} W{qt.bits} "
                     f"g{qt.group_size} has no kernel tiling at M={m}")


def check_served(eng, report, n_requests: int) -> None:
    done = report["requests"]
    if eng.cfg.paged_attn_impl != "fused":
        fail(f"paged_attn_impl is {eng.cfg.paged_attn_impl!r}, not 'fused'")
    if eng.n_kernel_fallbacks:
        fail(f"{eng.n_kernel_fallbacks} kernel fallback(s)")
    unfinished = [r.rid for r in done
                  if r.error or r.finished_at is None or not r.tokens]
    if len(done) != n_requests or unfinished:
        fail(f"{len(done)}/{n_requests} requests drained, unfinished "
             f"{unfinished}")
    say(f"served {len(done)} requests, all finished with tokens; "
        f"paged_attn_impl={eng.cfg.paged_attn_impl!r}, "
        f"n_kernel_fallbacks={eng.n_kernel_fallbacks}")


def phase_serve(cfg, flags) -> None:
    from repro.launch import serve
    from repro.utils.tree import tree_size_bytes

    args = serve.build_parser().parse_args(
        flags + ["--method", "gptq", "--bits", "4", "--group-size", "128"])
    say(f"phase B: {describe(cfg)}; GPTQ + norm tweak W4 g128, "
        f"{args.requests} greedy requests, int8 KV off")
    t0 = time.perf_counter()
    params = serve.build_params(cfg, args)
    qts = quantized_leaves(params)
    if not qts or any((qt.bits, qt.group_size) != (4, 128) for qt in qts):
        fail("the quantized tree is not uniformly W4 g128")
    check_tiled(qts)
    say(f"quantized {len(qts)} stacked projections to W4 g128 "
        f"({sum(qt.nbytes() for qt in qts) / 1e6:.1f} MB packed, "
        f"model {tree_size_bytes(params) / 1e6:.1f} MB) in "
        f"{time.perf_counter() - t0:.1f} s (one smoke run)")
    work = serve.make_workload(cfg, args)
    eng, report = serve.run_continuous(cfg, params, work, args)
    check_served(eng, report, len(work))


# ------------------------------------------------------------ --tp N

def prefill_logits(eng, prompts):
    """Last-position logits of one fresh prefill batch on a drained
    engine, through the engine's own (TP or single-device) prefill jit."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import engine as engine_mod

    b, n = prompts.shape
    slots = np.arange(b, dtype=np.int32)
    paged = {"bt_rows": jnp.asarray(eng.pool.tables[slots]),
             "slots": jnp.asarray(slots)}
    pos = jnp.asarray(np.broadcast_to(np.arange(n, dtype=np.int32), (b, n)))
    toks = jnp.asarray(prompts, jnp.int32)
    if eng.tp > 1:
        logits, eng.cache = engine_mod._paged_prefill_tp_jit(
            eng.cfg, eng.mesh, eng.params, toks, eng.cache, pos, paged)
    else:
        logits, eng.cache = engine_mod._paged_prefill_jit(
            eng.cfg, eng.params, toks, eng.cache, pos, paged)
    return np.asarray(logits, np.float32)


def device_bytes(tree) -> dict:
    """Bytes each device actually holds for a placed tree."""
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in getattr(leaf, "addressable_shards", ()):
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(out.items()))


def phase_tp(cfg, flags, tp: int) -> None:
    import jax
    import numpy as np

    from repro.core.quant.deploy import quantize_params_for_serving
    from repro.launch import serve
    from repro.models.transformer import init_lm
    from repro.serve.engine import ContinuousEngine

    say(f"phase TP: {describe(cfg)}; RTN W4 g128 served at tp={tp} on "
        f"{tp} devices, prefill logits against tp=1 on device 0")
    params = quantize_params_for_serving(
        cfg, init_lm(cfg, jax.random.PRNGKey(0)), bits=4, group_size=128)
    check_tiled(quantized_leaves(params))
    args = serve.build_parser().parse_args(
        flags + ["--method", "none", "--tp", str(tp)])
    work = serve.make_workload(cfg, args)
    eng_tp, report = serve.run_continuous(cfg, params, work, args)
    check_served(eng_tp, report, len(work))
    rep = eng_tp.tp_placement_report()
    if rep["replicated_quant_leaves"] or rep["replicated_pool_leaves"]:
        fail(f"replicated leaves under tp={tp}: "
             f"{rep['replicated_quant_leaves']} "
             f"{rep['replicated_pool_leaves']}")
    say(f"tp={tp} placement report: params "
        f"{rep['params']['per_device_bytes']} B/device of "
        f"{rep['params']['global_bytes']} B, KV pools "
        f"{rep['kv']['per_device_bytes']} B/device of "
        f"{rep['kv']['global_bytes']} B; no replicated quantized or pool "
        f"leaves")
    q_dev = device_bytes(quantized_leaves(eng_tp.params))
    say(f"tp={tp} packed weight bytes held per device id: {q_dev}")
    if len(q_dev) != tp or max(q_dev.values()) > 1.01 * min(q_dev.values()):
        fail(f"quantized leaves are not spread evenly over {tp} devices: "
             f"{q_dev}")
    eng_1 = ContinuousEngine(cfg, params, n_slots=args.slots,
                             max_len=eng_tp.spec.max_len,
                             page_size=args.page_size,
                             paged_attn=args.paged_attn)
    prompts = np.random.default_rng(1).integers(0, cfg.vocab_size,
                                                size=(args.slots, 32))
    l_tp = prefill_logits(eng_tp, prompts)
    l_1 = prefill_logits(eng_1, prompts)
    err = _rel_err(l_tp, l_1)
    agree = float(np.mean(l_tp.argmax(-1) == l_1.argmax(-1)))
    say(f"prefill logits tp={tp} vs tp=1: max|diff|/max|logit| {err:.3e} "
        f"(bound {TP_LOGIT_BOUND:g}), argmax agreement {agree:.3f}")
    if not err <= TP_LOGIT_BOUND:
        fail(f"tp={tp} prefill logits disagree with tp=1: {err:.3e}")


# ------------------------------------------------------------ main

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the serve phase at this TP width "
                         "against TP=1 (needs that many chips)")
    opts = ap.parse_args(argv)
    if os.environ.get("REPRO_DEQUANT_IMPL", ""):
        fail("REPRO_DEQUANT_IMPL is set; the smoke runs the kernels the "
             "backend picks, so unset it")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX reports {devices[0].platform} devices "
             f"only")
    if len(devices) < opts.tp:
        fail(f"--tp {opts.tp} needs {opts.tp} chips, found {len(devices)}")
    cache_dir = use_compile_cache()
    clock = CompileClock()

    from repro.configs import get_config

    cfg = get_config("llama3.2-1b")
    say(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache_dir}")
    t0 = time.perf_counter()
    if opts.tp > 1:
        phase_tp(cfg, SERVE_FLAGS, opts.tp)
    else:
        with jax.default_device(devices[0]):
            phase_kernels(cfg)
            phase_serve(cfg, SERVE_FLAGS)
    say(f"smoke run (not a benchmark): {time.perf_counter() - t0:.1f} s "
        f"wall, {clock.seconds:.1f} s in {clock.count} compiles or "
        f"compile-cache reads")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
