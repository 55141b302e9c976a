"""Median time to first token over every request due in the window: from
when it was due to when the step that produced its first token returned
(host clock)."""
from harness.stats import pct, ttfts


def read(run):
    v = pct(ttfts(run), 50)
    return None if v is None else v * 1e3
