"""The program's own host spans against the device trace.

The scheduler writes a ``serving.*`` span (``jax.profiler.TraceAnnotation``)
at each layer of a tick (``docs/DESIGN.md`` §6); they land on the host
plane of the same trace as the device's operations, on one clock.  Here
the first chip's idle time is put down to the spans the host was in, and
a named kernel's device time to the runs of the program that holds it.
Every function returns ``None`` when the trace has no such span, program
or kernel (a program without the spans, or a kernel off the path).
"""
from __future__ import annotations

import bisect

from harness import devtrace


def host_spans(events, name: str) -> list[tuple[float, float]]:
    """Merged (start, end) of the host spans called ``name``."""
    return devtrace.merge((e.start, e.end) for e in events
                          if e.name == name
                          and not e.plane.startswith(devtrace.DEVICE_PLANE))


def subtract(intervals, cut) -> list[tuple[float, float]]:
    """``intervals`` less ``cut`` (both merged and sorted)."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(cut) and cut[j][1] <= s:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < e:
            if cut[k][0] > s:
                out.append((s, cut[k][0]))
            s = max(s, cut[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def overlap(a, b) -> float:
    """Seconds that two merged, sorted interval lists share."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_share(run, inside: str, outside=()) -> float | None:
    """Per cent of the traced slice in which the first chip ran nothing
    while the host was in a span called ``inside`` and in none of those
    called ``outside``."""
    if not run.events or not run.trace_bounds:
        return None
    lo, hi = run.trace_bounds
    planes = devtrace.device_planes(run.events)[:1]
    region = host_spans(run.events, inside)
    if not planes or not region or hi <= lo:
        return None
    for name in outside:
        region = subtract(region, host_spans(run.events, name))
    gaps = devtrace.idle_gaps(run.events, lo, hi, planes[0])
    return 100.0 * overlap(gaps, region) / (hi - lo)


def kernel_seconds_per_run(run, program: str, kernel: str) -> float | None:
    """Device self time of the operations named ``<kernel>.N`` inside each
    run, starting in the traced slice, of the program whose module name
    contains ``program``, averaged over those runs (first chip)."""
    if not run.events or not run.trace_bounds:
        return None
    lo, hi = run.trace_bounds
    planes = devtrace.device_planes(run.events)[:1]
    if not planes:
        return None
    plane = planes[0]
    runs = [e for e in run.events if e.plane == plane
            and e.line == devtrace.MODULES_LINE and program in e.name
            and lo <= e.start < hi]
    ops = sorted((e for e in run.events if e.plane == plane
                  and e.line == devtrace.OPS_LINE), key=lambda e: e.start)
    starts = [e.start for e in ops]
    prefix, total, found = kernel + ".", 0.0, False
    for r in runs:
        inner = ops[bisect.bisect_left(starts, r.start):
                    bisect.bisect_right(starts, r.end)]
        for name, secs in devtrace.self_times(inner, r.start, r.end,
                                              plane).items():
            if name.startswith(prefix):
                total += secs
                found = True
    return total / len(runs) if found else None
