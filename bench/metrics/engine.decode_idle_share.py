"""Engine: per cent of the traced slice in which the chip ran nothing
while the host was in a decode tick (``serving.decode``: the token upload,
the dispatch, the host copy of the tokens, the eager ``advance`` and the
token bookkeeping)."""
from harness import program_spans


def read(run):
    return program_spans.idle_share(run, "serving.decode")
