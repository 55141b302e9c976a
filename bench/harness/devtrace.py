"""Reduce a profiler trace of the traced slice to device time.

``load`` flattens the ``.xplane.pb`` the JAX profiler writes into plain
``Event`` tuples; everything else works on those, so the reduction is
tested on synthesised events.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose ``XLA Modules`` line holds one event per
program run (named after the jitted function, e.g. ``jit__greedy_run``)
and whose ``XLA Ops`` line holds the operations inside them.  The
harness's host spans (``bench.*``) are on the host plane, on the same
clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import NamedTuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
CLOSE = SPAN_PREFIX + "window_close"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float            # seconds
    end: float


def load(log_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            short = line.name == OPS_LINE
            for ev in line.events:
                s = ev.start_ns * 1e-9
                out.append(Event(plane.name, line.name,
                                 op_name(ev.name) if short else ev.name, s,
                                 s + ev.duration_ns * 1e-9))
    return out


_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def op_name(text: str) -> str:
    """An XLA op's trace name, which on a TPU is its whole HLO text
    (``%fusion.12 = bf16[...] fusion(...), ...``), as the instruction's
    name and opcode: ``fusion.12 fusion``."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    m = _OPCODE.search(" " + rest)
    return f"{name} {m.group(1)}" if m else name


def spans(events) -> list[Event]:
    return [e for e in events if e.name.startswith(SPAN_PREFIX)]


def bounds(events) -> tuple[float, float] | None:
    """The traced slice of the window: from the first harness span to the
    window's close (its ``bench.window_close`` marker; the last span's end
    where it has none)."""
    sp = spans(events)
    if not sp:
        return None
    close = [e.start for e in sp if e.name == CLOSE]
    return (min(e.start for e in sp),
            min(close) if close else max(e.end for e in sp))


def device_planes(events) -> list[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PLANE)})


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(events, lo: float, hi: float, plane: str) -> list[tuple]:
    """Merged intervals in [lo, hi] in which an operation ran on
    ``plane``."""
    return merge(_clip([(e.start, e.end) for e in events
                        if e.plane == plane and e.line == OPS_LINE],
                       lo, hi))


def busy_seconds(events, lo: float, hi: float) -> float | None:
    """Seconds with an operation running, averaged over the chips."""
    planes = device_planes(events)
    if not planes:
        return None
    return sum(sum(e - s for s, e in busy(events, lo, hi, p))
               for p in planes) / len(planes)


def idle_gaps(events, lo: float, hi: float, plane: str):
    """(start, end) of each stretch of [lo, hi] with nothing running."""
    gaps, t = [], lo
    for s, e in busy(events, lo, hi, plane):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def module_runs(events, name: str, lo: float = float("-inf"),
                hi: float = float("inf")) -> list[float]:
    """Device seconds of each run, starting in [lo, hi), of the program
    whose module name contains ``name`` (``jit_<function>``), in time
    order (first chip)."""
    planes = device_planes(events)[:1]
    return [e.end - e.start for e in sorted(events, key=lambda e: e.start)
            if e.plane in planes and e.line == MODULES_LINE
            and name in e.name and lo <= e.start < hi]


def self_times(events, lo: float, hi: float, plane: str) -> dict:
    """Device seconds in [lo, hi] of each operation on ``plane`` by name,
    less the time of the operations nested in it (a ``while`` holds the
    operations of its body on the same line), so no second is counted
    twice."""
    ops = sorted(((max(e.start, lo), min(e.end, hi), e.name)
                  for e in events if e.plane == plane
                  and e.line == OPS_LINE and e.end > lo and e.start < hi),
                 key=lambda x: (x[0], -x[1]))
    tot: dict[str, float] = collections.defaultdict(float)
    stack: list[tuple[float, str]] = []      # (end, name) of open ops
    for s, e, name in ops:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and e <= stack[-1][0]:
            tot[stack[-1][1]] -= e - s
        tot[name] += e - s
        stack.append((e, name))
    return tot


def top_ops(events, lo: float, hi: float, k: int = 10):
    """The ``k`` operations with the most device self time in [lo, hi],
    under the trace's own names, as [name, seconds] (first chip)."""
    planes = device_planes(events)[:1]
    if not planes:
        return []
    tot = self_times(events, lo, hi, planes[0])
    return [[n, v] for n, v in
            sorted(tot.items(), key=lambda x: (-x[1], x[0]))[:k]]


def host_span_at(events, t: float) -> str:
    """The innermost harness span open at ``t`` (the latest to start of
    those covering it, the first to end among those), without its
    prefix; ``"none"`` if none is."""
    open_ = [e for e in spans(events) if e.start <= t < e.end]
    if not open_:
        return "none"
    best = max(open_, key=lambda e: (e.start, -e.end))
    return best.name[len(SPAN_PREFIX):]


def longest_gaps(events, lo: float, hi: float, k: int = 10):
    """The ``k`` longest idle gaps of the first chip as [what the host
    was doing, seconds]."""
    planes = device_planes(events)[:1]
    if not planes:
        return []
    gaps = sorted(idle_gaps(events, lo, hi, planes[0]),
                  key=lambda g: g[0] - g[1])[:k]
    return [[host_span_at(events, (s + e) / 2), e - s] for s, e in gaps]
