"""Device: share of the traced slice with no operation running on the
chip (1 - union of device-op intervals / slice), averaged over chips."""
from harness import devtrace


def read(run):
    if not run.events or not run.trace_bounds:
        return None
    lo, hi = run.trace_bounds
    busy = devtrace.busy_seconds(run.events, lo, hi)
    if busy is None or hi <= lo:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
