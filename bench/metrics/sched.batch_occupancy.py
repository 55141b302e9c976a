"""Scheduler: mean share of the decode batch's slots that were live, over
the window's decode ticks (the scheduler's ``n_active`` at each tick)."""
import numpy as np


def read(run):
    w = run.window
    live = [t.live for t in w.ticks if w.start <= t.start < w.end]
    if not live:
        return None
    return 100.0 * float(np.mean(live)) / run.cell.config["serving"]["slots"]
