"""Device time of the engine's paged decode-scan programs in the traced
window over the decode steps they ran."""
import devtrace

PROGRAM = "paged_decode_scan"


def read(run):
    t = run.data.get("tracker")
    if run.trace is None or t is None or not t.decode_steps:
        return None
    n, secs = devtrace.programs(run.trace, PROGRAM)
    if not n:
        return None
    return 1e3 * secs / t.decode_steps
