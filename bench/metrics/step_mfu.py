"""Model FLOPs of every prompt position prefilled and every token decoded
in the traced window (the reference module's yardstick: two per
multiply-add of each projection, the head once per prompt and per decoded
token, attention at each token's context) over the window's seconds times
the chip's bf16 peak, in %."""


def read(run):
    d = run.data
    t = d.get("tracker")
    if run.trace is None or t is None or not t.detail:
        return None
    ref, a = d["reference"], d["arch"]
    work = sum(ref.prompt_flops(a, s, e) for s, e, _ in t.prefill_chunks)
    work += t.first_tokens * ref.head_flops(a)
    work += sum(ref.token_flops(a, c) for c in t.decode_ctx)
    if not work:
        return None
    return 100.0 * work / (run.window_s * run.n_chips
                           * run.peaks["bf16_flops"])
