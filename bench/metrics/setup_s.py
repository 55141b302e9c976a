"""Process start to window start: weights, compile or cache load, warm-up
(host clock)."""


def read(run):
    return run.setup_s
