"""Output tokens that reached the host inside the window, per second of
the window (host clock)."""
from harness.stats import tokens_in_window


def read(run):
    return tokens_in_window(run) / (run.window.end - run.window.start)
