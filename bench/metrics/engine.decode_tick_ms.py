"""Engine: median host milliseconds of one decode tick (``_decode``, which
ends on a host copy of the tokens), over the window's ticks."""
import numpy as np


def read(run):
    w = run.window
    ms = [(t.end - t.start) * 1e3 for t in w.ticks
          if w.start <= t.start < w.end]
    return float(np.median(ms)) if ms else None
