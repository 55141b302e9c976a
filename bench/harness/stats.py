"""What the readers in ``metrics/`` share: the run they read, and the
sample sets every latency metric is taken over."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read of one run."""
    cell: object                 # spec.Cell
    shapes: object               # costs.Shapes
    peaks: dict
    seconds: float
    setup_s: float
    window: object               # serve.Window
    events: list | None = None   # devtrace.Event of the traced slice
    trace_bounds: tuple | None = None    # (lo, hi) on the trace's clock
    trace_host: tuple | None = None      # (start, end) on the host clock


def pct(xs, q: float) -> float | None:
    xs = np.asarray(xs, float)
    return float(np.percentile(xs, q)) if xs.size else None


def due_in_window(run: Run) -> list:
    """Records of the requests due in the window."""
    w = run.window
    return [r for r in w.records.values() if w.start <= r.due < w.end]


def ttfts(run: Run) -> list[float]:
    """Seconds from due to first token, for every request due in the
    window that got one."""
    return [r.first - r.due for r in due_in_window(run)
            if r.first is not None]


def gaps_in_window(run: Run) -> list[float]:
    """Every inter-token gap, of every request, that ends in the window."""
    w = run.window
    out = []
    for r in w.records.values():
        t = np.asarray(r.token_times)
        g = np.diff(t)
        out.extend(g[(t[1:] >= w.start) & (t[1:] <= w.end)].tolist())
    return out


def tokens_in_window(run: Run) -> int:
    w = run.window
    return sum(int(np.sum((np.asarray(r.token_times) >= w.start)
                          & (np.asarray(r.token_times) <= w.end)))
               for r in w.records.values())
