"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds BENCHMARK.json, `bench/` and the
program under test (`src/`). Set-up (weights drawn on the chip from the
seed, engine, warm-up of every shape the cell's traffic uses) is timed
as `setup_s`; then the window runs for `--seconds`; then the output
check compares what the window produced with the plain reference. The
last stdout line is the JSON result: with `--trace 0` the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics read from a
profiler trace of the window. Exits non-zero, with no result, without a
TPU or with fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness

    harness.set_env()

    spec = harness.benchmark_spec()
    cell = harness.cell_spec(spec, args.workload)
    workload = harness.load_json("workloads", args.workload)
    config = harness.load_json("configs", cell["config"])
    metrics = harness.cell_metrics(spec, args.workload, bool(args.trace))

    harness.use_compile_cache()
    dev = harness.device(cell["chips"])
    run = harness.driver(workload["driver"]).run(
        config=config, workload=workload, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t0=T0,
        peaks=harness.peaks(dev["kind"]))
    harness.emit(harness.report(run, metrics, dev, bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
