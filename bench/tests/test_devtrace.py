"""The reduction from a trace to device metrics, on synthesised events."""
import pytest

from harness import devtrace
from harness.devtrace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(line, name, s, e, plane=DEV):
    return Event(plane, line, name, s, e)


EVENTS = [
    # host spans: a decode tick, then a retire, then the window closes
    ev("python", "bench.step", 0.0, 10.0, HOST),
    ev("python", "bench.decode", 0.0, 6.0, HOST),
    ev("python", "bench.retire", 6.0, 9.0, HOST),
    ev("python", "bench.window_close", 10.0, 10.0, HOST),
    ev("python", "bench.step", 10.0, 12.0, HOST),      # the drain
    # two runs of the decode program, with ops inside
    ev(devtrace.MODULES_LINE, "jit__greedy_run(3)", 1.0, 3.0),
    ev(devtrace.OPS_LINE, "fusion.1", 1.0, 2.0),
    ev(devtrace.OPS_LINE, "tpu_custom_call.2", 1.5, 3.0),
    ev(devtrace.MODULES_LINE, "jit__greedy_run(3)", 4.0, 5.0),
    ev(devtrace.OPS_LINE, "fusion.1", 4.0, 5.0),
    ev(devtrace.MODULES_LINE, "jit_free_pages(9)", 7.0, 7.5),
    ev(devtrace.OPS_LINE, "scatter.4", 7.0, 7.5),
    ev(devtrace.OPS_LINE, "fusion.1", 11.0, 11.5),      # after the close
]


def test_bounds_end_at_the_close():
    assert devtrace.bounds(EVENTS) == (0.0, 10.0)
    no_close = [e for e in EVENTS if e.name != devtrace.CLOSE]
    assert devtrace.bounds(no_close) == (0.0, 12.0)


def test_busy_is_the_union_of_ops():
    assert devtrace.busy(EVENTS, 0.0, 10.0, DEV) == [
        (1.0, 3.0), (4.0, 5.0), (7.0, 7.5)]
    assert devtrace.busy_seconds(EVENTS, 0.0, 10.0) == pytest.approx(3.5)
    assert devtrace.busy_seconds(EVENTS, 2.0, 4.5) == pytest.approx(1.5)


def test_idle_gaps_and_what_the_host_did():
    assert devtrace.idle_gaps(EVENTS, 0.0, 10.0, DEV) == [
        (0.0, 1.0), (3.0, 4.0), (5.0, 7.0), (7.5, 10.0)]
    # each gap goes to the innermost span open at its midpoint
    assert devtrace.longest_gaps(EVENTS, 0.0, 10.0, k=4) == [
        ["retire", 2.5], ["retire", 2.0], ["decode", 1.0], ["decode", 1.0]]
    assert devtrace.host_span_at(EVENTS, 5.5) == "decode"
    assert devtrace.host_span_at(EVENTS, 6.5) == "retire"
    assert devtrace.host_span_at(EVENTS, 20.0) == "none"


def test_module_runs_by_jitted_name():
    assert devtrace.module_runs(EVENTS, "_greedy_run") == [2.0, 1.0]
    assert devtrace.module_runs(EVENTS, "_prefill_run") == []
    assert devtrace.module_runs(EVENTS, "_greedy_run", 3.5, 10.0) == [1.0]


def test_top_ops_inside_the_slice():
    top = devtrace.top_ops(EVENTS, 0.0, 10.0)
    assert top[0] == ["fusion.1", 2.0]
    assert top[1] == ["tpu_custom_call.2", 1.5]
    assert [n for n, _ in top] == ["fusion.1", "tpu_custom_call.2",
                                   "scatter.4"]


def test_top_ops_count_nested_ops_once():
    nested = [ev(devtrace.OPS_LINE, "while.3 while", 0.0, 4.0),
              ev(devtrace.OPS_LINE, "fusion.1 fusion", 0.5, 1.5),
              ev(devtrace.OPS_LINE, "custom.2 custom-call", 2.0, 3.5),
              ev(devtrace.OPS_LINE, "fusion.1 fusion", 5.0, 6.0)]
    assert devtrace.top_ops(nested, 0.0, 10.0) == [
        ["fusion.1 fusion", 2.0], ["custom.2 custom-call", 1.5],
        ["while.3 while", 1.5]]
    assert sum(v for _, v in devtrace.top_ops(nested, 0.0, 10.0)) == (
        devtrace.busy_seconds(nested, 0.0, 10.0))


def test_no_device_plane_reads_nothing():
    host = [e for e in EVENTS if e.plane == HOST]
    assert devtrace.busy_seconds(host, 0.0, 10.0) is None
    assert devtrace.longest_gaps(host, 0.0, 10.0) == []


def test_op_names_are_shortened_to_instruction_and_opcode():
    assert devtrace.op_name(
        "%fusion.12 = bf16[64,2048]{1,0:T(8,128)(2,1)} fusion(bf16[64] "
        "%p), kind=kLoop") == "fusion.12 fusion"
    assert devtrace.op_name(
        "%while.3 = (s32[]{:T(128)}, bf16[1,2]{1,0:T(8,128)(2,1)S(1)}) "
        "while((s32[]) %t), condition=%c") == "while.3 while"
    assert devtrace.op_name("%custom.1 = s8[4]{0} custom-call(s8[4] %x)"
                            ) == "custom.1 custom-call"
    assert devtrace.op_name("jit__greedy_run(123)") == "jit__greedy_run(123)"
