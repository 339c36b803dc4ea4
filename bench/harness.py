"""What every cell shares: finding its files by name, the device check,
the compile cache, metric readers, and the result line.

A cell `<cell>` of BENCHMARK.json is the workload file
`bench/workloads/<cell>.json`, which names its driver
(`bench/drivers/<driver>.py`) and its traffic or job parameters; its
configuration is `bench/configs/<config>.json`, whose `model_type` names
the plain reference (`bench/references/<model_type>.py`). Each metric
`<m>` of BENCHMARK.json is read by `bench/metrics/<m>.py`, whose `read(run)`
returns a number, or None where the run holds nothing to read. A
quantity split by the end-to-end metric it moves, `<q>.<part>` (the same
reading in cells that report different end-to-end metrics), is read by
`bench/metrics/<q>.<part>.py` where that file exists, else by
`bench/metrics/<q>.py`.

The reference module is the one place that knows a model; the harness,
the drivers, the output check and the metric readers reach the model only
through these names of it (`REFERENCE`), so a configuration of another
architecture joins by adding files:

  arch(config)         a frozen, hashable description; the harness reads
                       only its `vocab`, `bits` and `group_size`
  check_widths(a, cfg) raise ValueError unless the program's ModelConfig
                       runs the configuration's shapes
  base_key(seed)       the PRNG key of any seed
  global_leaves(a)     path -> (shape, kind) of the leaves outside blocks
  groups(a)            [(path, layers, leaves)]: each block group of the
                       program's param tree, "stack/p<j>" (blocks stacked
                       on a leading axis) or "prefix/<i>" (one block); the
                       global layer number of each block, prefix blocks
                       first; path -> (shape, kind) of one block's leaves
  draw(key, layer, path, shape, kind)
                       the float32 weight (layer -1: outside the blocks)
  logits(a, seed, tokens, rows, cols, dtype=None)
                       the plain float32 reference at `highest` precision
                       at the (row, col) positions of tokens; with `dtype`
                       the lower-precision control
  token_flops(a, context), prompt_flops(a, start, end), head_flops(a)
                       model FLOPs of a decoded token, a prompt span, the
                       head (bench/flops.py counts the kernels' own)
  dequant_projections(a)  [(K, N, calls per step)] of the dense packed
                       dequant matmuls a step makes, over all layers
  paged_reads(a)       [(KV heads, group, head dim, calls per step)] of the
                       paged attention reads a step makes

Weight kinds "linear" (K, N) and "experts" (E, K, N) are served packed
(bench/weights.py); every other kind in the configuration's param dtype.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache"


def set_env() -> None:
    """Environment of a run, set before JAX loads: compile counting on,
    kernel tiles from the program's fixed table (no tuning search, no
    tuning file), and the TPU runtime's own log files off (they would go
    to a fixed path outside the checkout)."""
    os.environ["REPRO_SANITIZE"] = "1"
    os.environ["REPRO_AUTOTUNE"] = "0"
    os.environ["REPRO_AUTOTUNE_CACHE"] = ""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def load_json(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def benchmark_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_spec(spec: dict, cell: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == cell:
            return w
    raise SystemExit(f"bench: no cell {cell!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics, or
    with `trace` its per-layer ones."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


REFERENCE = ("arch", "check_widths", "base_key", "global_leaves", "groups",
             "draw", "logits", "token_flops", "prompt_flops", "head_flops",
             "dequant_projections", "paged_reads")


def reference(config: dict):
    mod = importlib.import_module(f"references.{config['model_type']}")
    missing = [n for n in REFERENCE if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"bench: bench/references/{config['model_type']}"
                         f".py lacks {missing}")
    return mod


def _load_file(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return _load_file("drivers", name)


def metric_reader(name: str):
    if not (BENCH / "metrics" / f"{name}.py").exists():
        name = name.split(".")[0]
    return _load_file("metrics", name)


def peaks(kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json; its peaks are unknown")
    return table[kind]


def device(chips: int) -> dict:
    """The accelerator this run measures, or exit: no TPU, too few chips
    or a chip whose peaks are unknown is an error, never a fallback."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: JAX finds no device: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX reports {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX "
                         f"reports {len(devs)}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}
    peaks(info["kind"])
    return info


def use_compile_cache() -> None:
    """JAX's persistent compile cache at one fixed path in the checkout,
    so only a checkout's first run of a cell compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Backend compiles (persistent-cache reads included) that JAX runs
    while `active`, counted from its own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self.seconds = 0.0

        def listen(name, secs, **_):
            if self.active and name == self.EVENT:
                self.count += 1
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclasses.dataclass
class Check:
    """One number compared by the output check, beside its limit."""

    name: str
    value: float
    limit: float
    ok: bool


@dataclasses.dataclass
class Run:
    """What a driver hands to the metric readers."""

    setup_s: float
    window_s: float
    attempted: int
    failed: int
    checks: list[Check]
    memory_peak_bytes: int
    peaks: dict
    data: dict[str, Any]          # driver-specific records, by name
    trace: Optional[Any] = None   # devtrace.Trace of the traced window
    n_chips: int = 1


def nearest_rank(values: list[float], q: float) -> float:
    """The q-th percentile by nearest rank (a value that occurred)."""
    if not values:
        return math.nan
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def report(run: Run, metrics: list[dict], dev: dict, trace: bool) -> dict:
    out_metrics = {}
    for m in metrics:
        value = metric_reader(m["name"]).read(run)
        if value is None:
            continue
        out_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device_out = dict(dev, memory_peak_bytes=int(run.memory_peak_bytes))
    result = {"correct": all(c.ok for c in run.checks),
              "attempted": run.attempted, "failed": run.failed,
              "metrics": out_metrics, "device": device_out}
    if trace and run.trace is not None:
        from devtrace import busy_seconds, idle_gaps, top_ops

        device_out["busy_s"] = busy_seconds(run.trace)
        device_out["window_s"] = run.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in top_ops(run.trace)],
            "idle_gaps": [[n, s] for n, s in idle_gaps(run.trace)]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    return result


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
