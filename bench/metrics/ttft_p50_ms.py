"""Time to first token, p50 by nearest rank over every request due in the
window (latency.ttft), in ms."""
import harness
import latency


def read(run):
    vals = latency.ttft(run)
    return 1e3 * harness.nearest_rank(vals, 50) if vals else None
