"""Benchmark suite: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Heavy fixtures (the trained tiny
model) are cached under .cache/ — the first run trains it (~10 min CPU).

  table2  W4/W2 weight-only, GPTQ vs GPTQ+NT          (paper Table 2)
  table3  quantization runtime overhead               (paper Table 3)
  table4  NT on RTN / SmoothQuant, weight+act quant   (paper Table 4)
  table6  tweaking-iterations ablation                (paper Table 6)
  table8  calibration-data ablation                   (paper Table 8)
  table9  loss-function ablation                      (paper Table 9)
  fig1    per-layer activation-distribution gap       (paper Figure 1)
  kernels dequant-matmul microbench                   (deployment path)
  autotune  measured tile search + cache behavior + warm-cache serving
            (writes BENCH_autotune.json)
  quant_serve  quantized-vs-float serving + expert/W8A8 kernel rows
               (writes BENCH_quant_serve.json)
  spec    self-speculative decoding: W2/W3 draft + verify vs target-only
          (writes BENCH_spec.json)
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated table names (e.g. table2,fig1)")
    args = ap.parse_args()

    from benchmarks import (autotune_bench, fig1_distribution, kernels_bench,
                            paged_attn_bench, quant_serve_bench, spec_bench,
                            table2_weight_only,
                            table3_runtime, table4_ptq_methods, table6_iters,
                            table8_calibration, table9_losses, table10_awq)

    suites = {
        "table2": table2_weight_only.run,
        "table3": table3_runtime.run,
        "table4": table4_ptq_methods.run,
        "table6": table6_iters.run,
        "table8": table8_calibration.run,
        "table9": table9_losses.run,
        "table10": table10_awq.run,
        "fig1": fig1_distribution.run,
        "kernels": kernels_bench.run,
        "autotune": autotune_bench.run,
        "quant_serve": quant_serve_bench.run,
        "paged_attn": paged_attn_bench.run,
        "spec": spec_bench.run,
    }
    selected = (args.only.split(",") if args.only else list(suites))

    rows: list = []
    failed: list = []
    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.time()
        try:
            start = len(rows)
            suites[name](rows)
            for r in rows[start:]:
                print(",".join(str(x) for x in r), flush=True)
        except Exception as e:  # noqa: BLE001 - report, run the rest, fail
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        print(f"# {name} done in {time.time() - t0:.0f}s", file=sys.stderr)
    if failed:
        sys.exit(f"{len(failed)} suite(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
