"""Least time of the packed dequant-matmul calls of the traced window
(max of FLOPs over the bf16 peak and bytes over HBM bandwidth, from their
shapes: each dense projection the reference module lists, as many times
a step as it says, at M = slots in each decode step and M = the padded
chunk in each prefill call) over the device time of the kernel's events,
in %."""
import devtrace
import flops

KERNEL = "dequant_matmul_pallas"


def read(run):
    d = run.data
    t = d.get("tracker")
    if run.trace is None or t is None or not t.detail:
        return None
    n, secs = devtrace.kernel(run.trace, KERNEL)
    if not n:
        return None
    ref, a, pk = d["reference"], d["arch"], run.peaks

    def least(m):
        return sum(calls * flops.least_seconds(
            *flops.dequant_matmul_cost(m, k, nn, a.bits, a.group_size),
            pk["bf16_flops"], pk["hbm_bytes_per_s"])
            for k, nn, calls in ref.dequant_projections(a))

    total = t.decode_steps * least(d["n_slots"])
    total += sum(least(b) for _, _, b in t.prefill_chunks)
    return 100.0 * total / secs
