"""The readers of the program's own spans, on synthesised trace events."""
import types

import numpy as np
import pytest
from conftest import BENCH

from harness import devtrace, program_spans, spec
from harness.devtrace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"
IDLE_PARTS = ("engine.prefill_idle_share", "engine.decode_idle_share",
              "sched.idle_share")
KERNEL = "kernel.paged_decode_ms_per_tick"


def host(name, s, e):
    return Event(HOST, "python", name, s, e)


def dev(line, name, s, e):
    return Event(DEV, line, name, s, e)


def module(name, s, e):
    return dev(devtrace.MODULES_LINE, name, s, e)


def op(name, s, e):
    return dev(devtrace.OPS_LINE, name, s, e)


# two ticks in a 10 s slice: the first admits (prefill inside), both
# decode, retire and sample the pool; the host leaves ``step`` in
# [4, 5] and [8, 10]
EVENTS = [
    host("bench.step", 0.0, 4.0),
    host("serving.step", 0.0, 4.0),
    host("serving.admit", 0.0, 2.0),
    host("serving.prefill", 0.2, 1.8),
    host("serving.decode", 2.0, 3.5),
    host("serving.decode.wait", 3.0, 3.3),
    host("serving.retire", 3.5, 3.7),
    host("serving.occupancy", 3.7, 4.0),
    host("serving.step", 5.0, 8.0),
    host("serving.decode", 5.0, 7.0),
    host("serving.retire", 7.0, 7.5),
    host("serving.occupancy", 7.5, 8.0),
    host("bench.window_close", 10.0, 10.0),
    # a prefill chunk runs the paged kernel too: not a decode tick's
    module("jit__prefill_run(2)", 0.5, 1.5),
    op("paged_flash.1 custom-call", 0.5, 0.9),
    op("fusion.2 fusion", 0.9, 1.5),
    # decode ticks: the layer loop holds the kernel (self time only)
    module("jit__greedy_run(3)", 2.2, 3.2),
    op("while.3 while", 2.2, 3.2),
    op("paged_flash.4 custom-call", 2.3, 2.8),
    op("fusion.5 fusion", 2.8, 3.0),
    module("jit__greedy_run(3)", 5.5, 6.5),
    op("paged_flash.4 custom-call", 5.5, 5.9),
    op("fusion.5 fusion", 5.9, 6.5),
    module("jit_free(4)", 7.8, 7.9),
    op("scatter.6 scatter", 7.8, 7.9),
    # after the close: outside the slice
    module("jit__greedy_run(3)", 10.5, 11.0),
    op("paged_flash.4 custom-call", 10.5, 10.9),
]


def run_of(events, bounds=(0.0, 10.0)):
    return types.SimpleNamespace(events=events, trace_bounds=bounds)


def read(name, run):
    return spec.metric_reader(BENCH, name)(run)


def test_idle_parts_by_the_host_span_they_fall_in():
    # idle [0, .5], [1.5, 2.2], [3.2, 5.5], [6.5, 7.8], [7.9, 10]
    run = run_of(EVENTS)
    assert read("device.idle_share", run) == pytest.approx(69.0)
    assert read("engine.prefill_idle_share", run) == pytest.approx(10.0)
    assert read("engine.decode_idle_share", run) == pytest.approx(15.0)
    # retire and occupancy: [3.5, 4] and [7, 7.8] + [7.9, 8]
    assert read("sched.idle_share", run) == pytest.approx(14.0)


def test_kernel_self_time_inside_decode_runs_only():
    # 0.5 s and 0.4 s over the two decode runs that start in the slice;
    # not the prefill's kernel, not the enclosing while, not after close
    assert read(KERNEL, run_of(EVENTS)) == pytest.approx(450.0)
    assert read(KERNEL, run_of(EVENTS, (0.0, 4.0))) == pytest.approx(500.0)


def test_readers_find_nothing_in_a_program_without_spans_or_names():
    """The parent's trace: harness spans only, the kernel named after its
    remat scope.  Every reader returns None and none raises."""
    old = [e._replace(name=e.name.replace("paged_flash", "checkpoint"))
           for e in EVENTS if not e.name.startswith("serving.")]
    for name in IDLE_PARTS + (KERNEL,):
        assert read(name, run_of(old)) is None
        assert read(name, run_of([])) is None
        assert read(name, run_of(EVENTS, None)) is None


def test_subtract_and_overlap():
    assert program_spans.subtract([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == [
        (0, 1), (2, 4), (5, 9)]
    assert program_spans.subtract([(0, 3), (5, 8)], [(2, 6)]) == [
        (0, 2), (6, 8)]
    assert program_spans.subtract([(0, 3)], []) == [(0, 3)]
    assert program_spans.overlap([(0, 2), (3, 6)], [(1, 4), (5, 9)]) == 3


def _random_ticks(rng, n_ticks=12, grid=0.01):
    """Ticks of random length, each a ``serving.step`` whose children may
    be an admission and a decode, with host time between ticks, and
    device ops anywhere; every end lies on ``grid``."""
    ev, t = [], 0.0

    def at(x):
        return round(x / grid) * grid

    for _ in range(n_ticks):
        t0 = at(t + rng.integers(0, 30) * grid)
        a = at(t0 + rng.integers(0, 20) * grid)
        d = at(a + rng.integers(0, 20) * grid)
        t = at(d + rng.integers(1, 30) * grid)       # the step's end
        ev.append(host("serving.step", t0, t))
        if a > t0:
            ev.append(host("serving.admit", t0, a))
        if d > a:
            ev.append(host("serving.decode", a, d))
    hi = at(t + 0.1)
    for _ in range(40):
        s = at(rng.uniform(0, hi))
        ev.append(op("fusion.1 fusion", s, at(s + rng.integers(1, 15) * grid)))
    return ev, hi


@pytest.mark.parametrize("seed", range(5))
def test_idle_parts_do_not_overlap_and_stay_below_device_idle(seed):
    """On random ticks each idle part matches a count over grid cells, the
    parts cover each cell at most once, and their sum is the device's idle
    share less its idle time with the host outside ``serving.step``."""
    rng = np.random.default_rng(seed)
    events, hi = _random_ticks(rng)
    run = run_of(events, (0.0, hi))
    parts = {n: read(n, run) or 0.0 for n in IDLE_PARTS}

    grid = 0.01
    cells = (np.arange(round(hi / grid)) + 0.5) * grid

    def covered(name):
        return np.array([any(e.start <= c < e.end for e in events
                             if e.name == name) for c in cells])

    idle = ~covered("fusion.1 fusion")
    admit, decode = covered("serving.admit"), covered("serving.decode")
    step = covered("serving.step")
    want = {"engine.prefill_idle_share": idle & admit,
            "engine.decode_idle_share": idle & decode,
            "sched.idle_share": idle & step & ~admit & ~decode}
    assert not (want["engine.prefill_idle_share"]
                & want["engine.decode_idle_share"]).any()
    for name, cellset in want.items():
        assert parts[name] == pytest.approx(100 * cellset.mean(), abs=1e-6)
    device_idle = read("device.idle_share", run)
    assert device_idle == pytest.approx(100 * idle.mean(), abs=1e-6)
    assert sum(parts.values()) == pytest.approx(
        100 * (idle & step).mean(), abs=1e-6)
    assert sum(parts.values()) <= device_idle + 1e-6
