"""Scheduler: 95th percentile, over requests due in the window, of the
time from when a request was due to the start of its admission prefill
(harness span around ``Scheduler._prefill_slot``; host clock)."""
from harness.stats import due_in_window, pct


def read(run):
    waits = [r.prefill[0] - r.due for r in due_in_window(run)
             if r.prefill is not None]
    v = pct(waits, 95)
    return None if v is None else v * 1e3
