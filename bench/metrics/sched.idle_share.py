"""Scheduler: per cent of the traced slice in which the chip ran nothing
while the host was in ``Scheduler.step`` (``serving.step``) but in neither
an admission nor a decode tick: retirement, the occupancy sample and the
glue between them.  With the two engine idle shares it makes up
``device.idle_share`` less the idle time with the host outside ``step``."""
from harness import program_spans


def read(run):
    return program_spans.idle_share(run, "serving.step",
                                    ("serving.admit", "serving.decode"))
