"""95th percentile over every inter-token gap, of every request, that
ends inside the window (host clock)."""
from harness.stats import gaps_in_window, pct


def read(run):
    v = pct(gaps_in_window(run), 95)
    return None if v is None else v * 1e3
