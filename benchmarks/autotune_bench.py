"""Autotuner benchmark, recorded to BENCH_autotune.json.

Exercises the full REPRO_AUTOTUNE=1 machinery end to end on small shapes:
the measured candidate search (real ``pallas_call`` timings — interpret
mode on CPU, so the absolute numbers and win ratios are only meaningful on
TPU; what this records on CPU is the search cost and that the plumbing
selects, persists, and re-serves plans), the warm-cache resolution cost in
the default mode, and a tiny serving run proving a warm cache drives the
engine without recompiles or fallbacks.

Sections of the JSON:
  search   — per shape class: candidate count, search wall time, the table
             plan, the measured winner, and timed table-vs-winner us
  paged    — same for the page-walk tile at an oversized page size
  warm     — cache-hit resolution latency (us) vs the cold table lookup
  serve    — greedy tok/s of a tiny engine under the deterministic table
             vs a warm measured cache (same tokens asserted)
"""
from __future__ import annotations

import json
import os
import tempfile
import time

import jax
import numpy as np

from repro.kernels import autotune, template

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BENCH_autotune.json")

# (kind, m, k, n, bits, group_size) — decode-skinny and prefill classes
# across the kernel families
SHAPES = [
    ("dequant", 8, 256, 256, 4, 64),
    ("dequant", 64, 512, 256, 4, 128),
    ("w8a8", 8, 256, 256, 8, -1),
]


def _timed_plan(kind, m, k, n, bits, gs, plan):
    kernel_fn = autotune._MEASURE_FNS[kind]()
    rng = np.random.default_rng(0)
    mb = max(autotune.m_bucket(m), 8)
    g = 1 if gs == -1 else k // gs
    pk = template.packed_tile_rows(k, bits)
    qw = rng.integers(0, 256, (pk, n)).astype(np.uint8)
    scale = rng.uniform(0.01, 0.1, (g, n)).astype(np.float32)
    if kind.endswith("w8a8"):
        x = rng.integers(-127, 128, (mb, k)).astype(np.int8)
    else:
        x = rng.normal(size=(mb, k)).astype(np.float32)
    bm, bn, bk = plan
    pad = (-mb) % bm
    xp = np.pad(x, ((0, pad), (0, 0)))
    return autotune._time_candidate(lambda: kernel_fn(
        xp, qw, scale, bits=bits, group_size=gs, bm=bm, bn=bn, bk=bk,
        interpret=jax.default_backend() != "tpu"))


def _serve_tok_s(cache_path: str | None):
    from repro.configs import TINY
    from repro.models.transformer import init_lm
    from repro.serve.engine import ContinuousEngine

    if cache_path is None:
        os.environ["REPRO_AUTOTUNE"] = "0"
        os.environ.pop("REPRO_AUTOTUNE_CACHE", None)
    else:
        os.environ["REPRO_AUTOTUNE"] = ""
        os.environ["REPRO_AUTOTUNE_CACHE"] = cache_path
    autotune.reset()
    cfg = TINY.replace(n_repeats=2, d_model=64, head_dim=16, d_ff=128)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, params, n_slots=3, max_len=64, page_size=16,
                           prefill_bucket=8, chunked_prefill=16)
    rng = np.random.default_rng(0)
    for i in range(6):
        eng.submit(rng.integers(0, cfg.vocab_size, 24), max_new=8,
                   arrival=float(i))
    t0 = time.time()
    done = eng.run(max_steps=2000)
    dt = time.time() - t0
    toks = {r.rid: r.tokens for r in done}
    return sum(len(t) for t in toks.values()) / dt, toks


def run(rows: list):
    out = {"template_version": template.TEMPLATE_VERSION,
           "backend": jax.default_backend(),
           "note": ("interpret-mode timings on CPU: search machinery and "
                    "cache behavior are what is measured; win ratios are "
                    "only meaningful on TPU"),
           "search": {}, "paged": {}, "warm": {}, "serve": {}}
    saved = {k: os.environ.get(k) for k in ("REPRO_AUTOTUNE",
                                            "REPRO_AUTOTUNE_CACHE")}
    tmp = tempfile.mkdtemp(prefix="repro_autotune_bench_")
    cache = os.path.join(tmp, "tune.json")
    try:
        os.environ["REPRO_AUTOTUNE"] = "1"
        os.environ["REPRO_AUTOTUNE_CACHE"] = cache
        autotune.reset()
        for kind, m, k, n, bits, gs in SHAPES:
            key = autotune.matmul_key(kind, m, k, n, bits, gs)
            table = autotune.fallback_matmul_plan(
                m, k, n, kind=kind, bits=bits, group_size=gs, bm=128,
                bn=256, bk=256)
            n_cands = len(autotune._matmul_candidates(m, k, n, bits, gs,
                                                      table))
            t0 = time.time()
            tuned = autotune.matmul_plan(kind, m, k, n, bits=bits,
                                         group_size=gs)
            search_s = time.time() - t0
            t_table = _timed_plan(kind, m, k, n, bits, gs, table)
            t_tuned = _timed_plan(kind, m, k, n, bits, gs, tuned)
            out["search"][key] = {
                "candidates": n_cands,
                "search_s": round(search_s, 3),
                "table_plan": list(table),
                "tuned_plan": list(tuned),
                "table_us": round(t_table * 1e6, 1),
                "tuned_us": round(t_tuned * 1e6, 1),
                "win": round(t_table / max(t_tuned, 1e-12), 3),
            }
            rows.append((f"autotune/search_{key}", search_s * 1e6,
                         f"candidates={n_cands};tuned={tuned};"
                         f"table={table}"))
        # paged tile search at an oversized page size (real candidates)
        t0 = time.time()
        tile = autotune.paged_tile(512, "bf16", 1)
        out["paged"]["paged:ps512:kvbf16:m8"] = {
            "search_s": round(time.time() - t0, 3),
            "table_tile": autotune.fallback_paged_tile(512),
            "tuned_tile": tile,
        }
        rows.append(("autotune/search_paged_ps512", (time.time() - t0) * 1e6,
                     f"tuned_tile={tile}"))

        # warm-cache resolution latency vs the deterministic table
        os.environ["REPRO_AUTOTUNE"] = ""
        autotune.reset()
        kind, m, k, n, bits, gs = SHAPES[0]
        reps = 200
        t0 = time.perf_counter()
        for _ in range(reps):
            autotune.matmul_plan(kind, m, k, n, bits=bits, group_size=gs)
        warm_us = (time.perf_counter() - t0) / reps * 1e6
        os.environ["REPRO_AUTOTUNE"] = "0"
        t0 = time.perf_counter()
        for _ in range(reps):
            autotune.matmul_plan(kind, m, k, n, bits=bits, group_size=gs)
        table_us = (time.perf_counter() - t0) / reps * 1e6
        out["warm"] = {"cache_hit_us": round(warm_us, 2),
                       "table_us": round(table_us, 2)}
        rows.append(("autotune/warm_cache_hit", warm_us,
                     f"table_us={table_us:.2f}"))

        # tiny serving run: table mode vs warm cache, same greedy tokens
        tok_table, toks_a = _serve_tok_s(None)
        tok_warm, toks_b = _serve_tok_s(cache)
        assert toks_a == toks_b, "warm autotune cache changed greedy tokens"
        out["serve"] = {"table_tok_s": round(tok_table, 1),
                        "warm_cache_tok_s": round(tok_warm, 1),
                        "tokens_identical": True}
        rows.append(("autotune/serve_warm_cache_tok_s", 1e6 / max(tok_warm,
                                                                  1e-9),
                     f"table_tok_s={tok_table:.1f};"
                     f"warm_tok_s={tok_warm:.1f};tokens_identical=True"))
    finally:
        for k_, v in saved.items():
            if v is None:
                os.environ.pop(k_, None)
            else:
                os.environ[k_] = v
        autotune.reset()
    with open(OUT, "w") as f:
        json.dump(out, f, indent=2)
    return rows


if __name__ == "__main__":
    out = []
    run(out)
    for r in out:
        print(",".join(str(x) for x in r))
