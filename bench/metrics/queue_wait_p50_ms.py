"""Median (nearest rank) over requests due in the window and admitted
of admission time minus scheduled arrival, in ms. The engine stamps admission with
the benchmark's clock reading that was passed to the step admitting it."""
import harness
import latency


def read(run):
    vals = [r.admitted - r.arrival for r in latency.due(run)
            if r.admitted is not None]
    if not vals:
        return None
    return 1e3 * harness.nearest_rank(vals, 50)
