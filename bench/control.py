"""Readings that a cell's output-check limit is set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 45

For each seed, in one process, a whole run of the cell (weights from the
seed, pre-roll, window, drain) whose output check puts the control in
the program's place: the plain reference computed in float8 (e4m3), the
precision below the configuration's bfloat16 (the reference module's
`logits` with `dtype`), read for the tokens the control ranks first.
The run is judged by the cell's own limits through the harness, so a
sound limit reports `correct: false` for it; the program's readings of
the same sample are printed beside. A limit goes between the largest program reading and
the smallest control reading. Needs the chip, like bench/run.py; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CONTROL = "float8_e4m3fn"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=45.0)
    args = p.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
    import harness

    harness.set_env()
    spec = harness.benchmark_spec()
    cell = harness.cell_spec(spec, args.workload)
    workload = harness.load_json("workloads", args.workload)
    config = harness.load_json("configs", cell["config"])
    harness.use_compile_cache()
    dev = harness.device(cell["chips"])
    serve = harness.driver(workload["driver"])
    readings = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run = serve.run(config=config, workload=workload, seed=seed,
                        seconds=args.seconds, trace=False, t0=t0,
                        peaks=harness.peaks(dev["kind"]), control=CONTROL)
        res = harness.report(run, [], dev, False)
        r = run.data["readings"]
        readings.append(r)
        print("control " + json.dumps(dict(
            seed=seed, correct=res["correct"], checks=res["checks"],
            program=r, seconds=time.perf_counter() - t0)), flush=True)
    for key in ("gap_max", "gap_mean"):
        prog = max(r[key] for r in readings)
        ctl = min(r["control_" + key] for r in readings)
        print(f"control summary {key}: program max {prog!r} over "
              f"{len(readings)} seeds; control min {ctl!r}, ratio "
              f"{ctl / max(prog, 1e-30):.2f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
