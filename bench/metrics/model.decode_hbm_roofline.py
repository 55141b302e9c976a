"""Model step: the decode program's share of its roofline.  The least
time a tick needs, the larger of its least HBM bytes over the HBM peak and
its operations over the int8 peak (``harness.costs``: every weight as
stored, each live row's K/V context), over the device time per run of the
``_greedy_run`` program in the traced slice.  At these shapes the bytes
bound it."""
from harness import costs, devtrace

PROGRAM = "_greedy_run"


def read(run):
    if not run.events or not run.trace_bounds or not run.peaks:
        return None
    runs = devtrace.module_runs(run.events, PROGRAM, *run.trace_bounds)
    h0, h1 = run.trace_host
    ticks = [t for t in run.window.ticks if h0 <= t.start and t.end <= h1]
    if not runs or not ticks:
        return None
    s, p = run.shapes, run.peaks
    least = sum(max(costs.decode_tick_bytes(s, t.contexts)
                    / p["hbm_bytes_per_s"],
                    sum(costs.decode_ops(s, c) for c in t.contexts)
                    / p["int8_ops_per_s"]) for t in ticks) / len(ticks)
    device = sum(runs) / len(runs)
    return 100.0 * least / device
