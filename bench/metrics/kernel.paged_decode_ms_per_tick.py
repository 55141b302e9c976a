"""Kernels: device milliseconds of the paged flash-decode kernel (self
time of the ops named ``paged_flash.N``) per run of the decode program
(``_greedy_run``, one run a tick) in the traced slice."""
from harness import program_spans


def read(run):
    secs = program_spans.kernel_seconds_per_run(run, "_greedy_run",
                                                "paged_flash")
    return None if secs is None else 1e3 * secs
