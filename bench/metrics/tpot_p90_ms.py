"""Time per output token after the first, p90 by nearest rank over the
requests due in the window (latency.tpot), in ms."""
import harness
import latency


def read(run):
    vals = latency.tpot(run)
    return 1e3 * harness.nearest_rank(vals, 90) if vals else None
