"""Tensor-parallel serving benchmark -> BENCH_tp_serve.json.

Runs the W4 GQA serving workload on the continuous engine at TP=1/2/4 over
the first four devices: measured tokens/s per width (orientation only on
CPU — four virtual devices share the same socket and the psums are
memcpys, so TP *costs* time there), greedy-token identity asserted against
TP=1, and the deployment story the placement actually buys: per-device
bytes for packed weights and KV pools from the live buffer shardings —
on a real mesh that is the per-device HBM footprint, which is what lets a
norm-tweaked W4 checkpoint of a model N x too big for one device serve at
all (the paper's low-bit deployment regime at scale).

It runs on whatever devices JAX finds (four are needed). The CPU
rehearsal gives the CPU backend four virtual devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src:. python benchmarks/tp_serve_bench.py
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.configs import TINY
from repro.models.transformer import init_lm
from repro.serve.engine import ContinuousEngine

N_SLOTS = 4
N_REQUESTS = 12
N_REPS = 3
QUANT_BITS = 4
QUANT_GROUP = 32
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BENCH_tp_serve.json")


def make_cfg():
    # GQA geometry with kv-head headroom so every measured width divides it
    return TINY.replace(d_model=256, n_heads=8, n_kv_heads=4, head_dim=32,
                        d_ff=512, n_repeats=4)


def make_workload(cfg, seed=0):
    rng = np.random.default_rng(seed)
    work = []
    for _ in range(N_REQUESTS):
        plen = int(rng.integers(8, 33))
        mnew = int(rng.integers(8, 25))
        work.append((rng.integers(0, cfg.vocab_size, plen), mnew))
    return work


def run_engine(cfg, params, work, tp):
    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_len=96,
                           page_size=16, prefill_bucket=16, tp=tp,
                           quant_bits=QUANT_BITS, quant_group=QUANT_GROUP)
    for prompt, mnew in work:
        eng.submit(prompt, max_new=mnew)
    done = eng.run(max_steps=100_000)               # warm-up + tokens
    tokens = [r.tokens for r in done]
    times = []
    for _ in range(N_REPS):
        for prompt, mnew in work:
            eng.submit(prompt, max_new=mnew)
        t0 = time.time()
        rep = eng.run(max_steps=100_000)
        times.append(time.time() - t0)
        assert [r.tokens for r in rep] == tokens, "rep diverged"
    total = sum(len(t) for t in tokens)
    return tokens, total / min(times), eng.tp_placement_report()


def main():
    assert len(jax.devices()) >= 4, "needs four devices (see the docstring)"
    cfg = make_cfg()
    params = init_lm(cfg, jax.random.PRNGKey(0))
    work = make_workload(cfg)
    rows = []
    base_tokens = None
    for tp in (1, 2, 4):
        tokens, tps, rep = run_engine(cfg, params, work, tp)
        if base_tokens is None:
            base_tokens = tokens
        else:
            assert tokens == base_tokens, f"tp={tp} tokens diverged from tp=1"
        assert not rep["replicated_quant_leaves"], rep
        assert not rep["replicated_pool_leaves"], rep
        row = {
            "tp": tp,
            "tokens_per_s_cpu_measured": round(tps, 2),
            "params_bytes_per_device": rep["params"]["per_device_bytes"],
            "params_bytes_global": rep["params"]["global_bytes"],
            "kv_pool_bytes_per_device": rep["kv"]["per_device_bytes"],
            "kv_pool_bytes_global": rep["kv"]["global_bytes"],
            "greedy_tokens_identical_to_tp1": True,
        }
        rows.append(row)
        print(f"tp={tp}: {tps:7.1f} tok/s (CPU), "
              f"{row['params_bytes_per_device'] / 1e6:.2f} MB params/dev, "
              f"{row['kv_pool_bytes_per_device'] / 1e6:.2f} MB KV/dev")
    out = {
        "bench": "tp_serve",
        "config": {"arch": "tiny-gqa", "d_model": cfg.d_model,
                   "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
                   "n_layers": cfg.n_layers, "quant_bits": QUANT_BITS,
                   "quant_group": QUANT_GROUP, "n_slots": N_SLOTS,
                   "n_requests": N_REQUESTS},
        "note": ("measured tok/s on a forced 4-device CPU host mesh — "
                 "collectives are memcpys on one socket, so TP costs "
                 "wall-clock here; the deployment signal is the per-device "
                 "byte columns (HBM footprint on a real mesh) plus the "
                 "asserted greedy-token identity"),
        "rows": rows,
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
