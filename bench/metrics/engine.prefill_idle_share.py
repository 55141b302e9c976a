"""Engine: per cent of the traced slice in which the chip ran nothing
while the host was admitting a request (``serving.admit``: the pool
claim, the prefill's slot view, chunk dispatches and merge, and the
first token's read)."""
from harness import program_spans


def read(run):
    return program_spans.idle_share(run, "serving.admit")
