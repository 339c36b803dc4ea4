"""Least time of the fused paged-attention reads of the traced window
(decode: one query row per slot against its live tokens; chunked prefill
after the first chunk: the padded chunk's rows against the slot's filled
tokens; each read the reference module lists, as many times a step as it
says), from their shapes, over the device time of the kernel's events,
in %."""
import devtrace
import flops

KERNEL = "paged_attention_pallas"


def read(run):
    d = run.data
    t = d.get("tracker")
    if run.trace is None or t is None or not t.detail:
        return None
    n, secs = devtrace.kernel(run.trace, KERNEL)
    if not n:
        return None
    ref, a, pk = d["reference"], d["arch"], run.peaks

    def least(rows, live):
        return sum(calls * flops.least_seconds(
            *flops.paged_read_cost(rows, live, kv_heads, g, hd),
            pk["bf16_flops"], pk["hbm_bytes_per_s"])
            for kv_heads, g, hd, calls in ref.paged_reads(a))

    total = sum(least(1, c) for c in t.decode_ctx)
    total += sum(least(b, e) for s, e, b in t.prefill_chunks if s > 0)
    return 100.0 * total / secs
