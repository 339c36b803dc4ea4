"""1 - (union of the intervals in which an operation ran on the chip) /
(the traced window)."""
import devtrace


def read(run):
    if run.trace is None or not run.trace.ops or "tracker" not in run.data:
        return None
    return 1.0 - devtrace.busy_seconds(run.trace) / run.window_s
