"""Model FLOPs of every prompt position prefilled and every token decoded
in the traced window (flops.py: two per multiply-add of each projection,
the head once per prompt and per decoded token, attention at each token's
context) over the window's seconds times the chip's bf16 peak, in %."""
import flops


def read(run):
    d = run.data
    t = d.get("tracker")
    if run.trace is None or t is None or not t.detail:
        return None
    a = d["arch"]
    work = sum(flops.prompt_flops(a, s, e) for s, e, _ in t.prefill_chunks)
    work += t.first_tokens * flops.head_flops(a)
    work += sum(flops.token_flops(a, c) for c in t.decode_ctx)
    if not work:
        return None
    return 100.0 * work / (run.window_s * run.n_chips
                           * run.peaks["bf16_flops"])
