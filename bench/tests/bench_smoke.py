"""Smoke-size configurations and workloads for the benchmark's CPU tests,
for every cell and configuration that BENCHMARK.json names.

Importing this module puts the benchmark's directory and the program's
sources on sys.path: the harness imports its modules by their names in
bench/."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SPEC = harness.benchmark_spec()
# every cell and configuration of BENCHMARK.json, by name
CELLS = {w["name"]: w["config"] for w in SPEC["workloads"]}
CONFIGS = sorted({c["name"] for c in SPEC["configs"]})
SERVE_CELLS = sorted(c for c in CELLS
                     if harness.load_json("workloads", c)["driver"] == "serve")


def smoke(name):
    """The configuration at the smoke widths of its file
    `bench/configs/<name>.smoke.json` (group 32). Returns (configuration,
    reference module, its description of the model, program config)."""
    sizes = harness.load_json("configs", f"{name}.smoke")
    cfg = harness.load_json("configs", name)
    cfg.update(sizes["config"])
    cfg["quant"] = dict(cfg["quant"], group_size=32)
    cfg["program"]["overrides"].update(sizes["program"], serve_quant_group=32)
    ref = harness.reference(cfg)
    program_config = harness.driver("serve").program_config
    return cfg, ref, ref.arch(cfg), program_config(cfg)


def smoke_workload(cell: str) -> dict:
    """`cell`'s workload at smoke size: 4 slots, short prompts and
    answers, a few seconds of arrivals after a pre-roll of at most one."""
    wl = harness.load_json("workloads", cell)
    wl["engine"].update(n_slots=4, chunked_prefill=16, prefill_bucket=16)
    t = wl["traffic"]
    if t["arrivals"]["kind"] == "poisson":
        t["arrivals"] = {"kind": "poisson", "rate": 6.0}
        t["preroll_seconds"] = min(t.get("preroll_seconds", 0), 1.0)
        t["drain_seconds"] = min(t.get("drain_seconds", 0), 30.0)
    else:
        t["arrivals"] = {"kind": "backlog", "n": 12}
    t["prompt"] = {"law": "lognormal", "median": 20, "sigma": 0.5,
                   "min": 8, "max": 40}
    t["output"] = {"law": "lognormal", "median": 16, "sigma": 0.5,
                   "min": 8, "max": 24}
    # the cells' limits are set from readings at their own sizes; at
    # smoke size over ~100 positions the program reads gap_max <= 0.031
    # and gap_mean <= 0.0004, the float8 control gap_max >= 0.19 and
    # gap_mean >= 0.0085 (three seeds each)
    limit = {"gap_max": 0.1, "gap_mean": 0.003}[wl["check"]["compare"]]
    wl["check"].update(min_positions=8, limit=limit)
    return wl
