"""Least time of the grouped expert dequant-matmul calls of the traced
window over the device time of the kernel's events (its whole op name),
in %. Each MoE layer call of a prefill or decode step the window started
makes the grouped products the reference module lists
(`expert_projections`: (K, N, calls per MoE layer call)); each reads the
packed weights of the experts that got rows once and their rows' inputs
and outputs (flops.dequant_matmul_cost over those experts at the rows
they got, from the engine's routing counts, bench/moelog.py). A log entry
sums a prefill call's or a decode block's layer calls, and its least time
is taken over those sums, which can only lower the numerator: the share
is a lower bound."""
import devtrace
import flops
import moelog

KERNEL = "grouped_expert_dequant_pallas"


def read(run):
    d = run.data
    ref = d.get("reference")
    if run.trace is None or not hasattr(ref, "expert_projections"):
        return None
    n, secs = devtrace.kernel(run.trace, KERNEL)
    log = moelog.entries(run)
    if not n or not log:
        return None
    a, pk = d["arch"], run.peaks
    total = 0.0
    for _, _, _, calls, rows, hit, _ in log:
        if not hit:
            continue
        for k, nn, per_call in ref.expert_projections(a):
            f, b = flops.dequant_matmul_cost(rows / hit, k, nn, a.bits,
                                             a.group_size)
            total += calls * per_call * flops.least_seconds(
                f * hit / calls, b * hit / calls, pk["bf16_flops"],
                pk["hbm_bytes_per_s"])
    return 100.0 * total / secs
