"""Per-request latencies of a serving run, in seconds, for the metric
readers. Both cover the requests due in the window (scheduled arrival in
[0, window)), each followed until the loop ended; stamps are the
benchmark's clock after the step that made the token."""


def due(run) -> list:
    d = run.data
    return [r for r in d.get("records", ()) if 0 <= r.arrival < d["seconds"]]


def ttft(run) -> list[float]:
    """First stamp minus scheduled arrival (open loop). A request with no
    first token when the loop ends counts at the loop's end, the least
    its time to first token can be. Empty without a drain, where the
    requests due late in the window are not followed."""
    if not run.data.get("drain"):
        return []
    end = run.data["loop_end_s"]
    return [(r.t_first if r.t_first is not None else end) - r.arrival
            for r in due(run)]


def tpot(run) -> list[float]:
    """(last stamp - first stamp) / (tokens between them), over each
    request that got tokens from two steps or more."""
    return [(r.t_last - r.t_first) / (r.n_tokens - r.n_first)
            for r in due(run)
            if r.t_first is not None and r.n_tokens > r.n_first]
