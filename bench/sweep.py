"""Find the knee of a serving cell: its traffic at several fixed rates.

    python3 bench/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20 --seed <n>

One process sets the cell up once, then runs one open-loop window per
rate (after the cell's pre-roll, and followed by a drain of up to the
cell's `drain_seconds`) and prints, per rate, the requests due in the
window and finished, the queue left when the window closed, TTFT and
TPOT medians and tails and the output rate. The knee is the
highest rate whose queue stays short and whose TTFT does not grow with
the window; a cell below it is run at about four fifths of it, with the
rate written into the workload file. Needs the chip, like bench/run.py.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
    import harness

    harness.set_env()
    import traffic

    spec = harness.benchmark_spec()
    cell = harness.cell_spec(spec, args.workload)
    workload = harness.load_json("workloads", args.workload)
    config = harness.load_json("configs", cell["config"])
    harness.use_compile_cache()
    harness.device(cell["chips"])
    serve = harness.driver(workload["driver"])
    _, a, eng = serve.prepare(config, workload, args.seed)
    drain = float(workload["traffic"].get("drain_seconds", 0)) or 60.0
    for rate in [float(r) for r in args.rates.split(",")]:
        t = copy.deepcopy(workload["traffic"])
        t["arrivals"] = {"kind": "poisson", "rate": rate}
        items = traffic.make(t, args.seed, args.seconds, a.vocab)
        tracker = serve.submit(eng, items, False,
                               deadline=args.seconds + drain + 1.0)
        w_open, w_end, end = serve.window(
            eng, tracker, items, args.seconds, drain,
            lambda name: contextlib.nullcontext(), lambda: None,
            lambda: None, start=-traffic.preroll(t))
        recs = [r for _, r in tracker.pairs if 0 <= r.arrival < args.seconds]
        queued = sum(1 for r in recs if r.admitted is None
                     or r.admitted > w_end)
        done = sum(1 for r in recs if r.n_tokens >= r.max_new)
        ttft = [(r.t_first if r.t_first is not None else end) - r.arrival
                for r in recs]
        tpot = [(r.t_last - r.t_first) / (r.n_tokens - r.n_first)
                for r in recs if r.n_tokens > r.n_first]
        print(f"sweep rate {rate:g}/s: due {len(recs)} finished {done} "
              f"queued at close {queued} ttft p50 "
              f"{harness.nearest_rank(ttft, 50):.3f} s p90 "
              f"{harness.nearest_rank(ttft, 90):.3f} s tpot p50 "
              f"{1e3 * harness.nearest_rank(tpot, 50):.1f} ms p90 "
              f"{1e3 * harness.nearest_rank(tpot, 90):.1f} ms out "
              f"{tracker.tokens_in_window / (w_end - w_open):.0f} tok/s "
              f"drain {end - w_end:.1f} s", flush=True)
        # cancel what the loop left running, so each rate starts from an
        # empty engine
        while not eng.sched.all_done():
            eng.step(1e9)
        eng.sched.drain_finished()
    return 0


if __name__ == "__main__":
    sys.exit(main())
