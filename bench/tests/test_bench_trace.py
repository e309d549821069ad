"""The trace reduction: busy and idle, per-op self time, gap naming, on
hand-made events and on a small trace recorded on a v5e chip."""
import os

import pytest

import _paths
from bench import trace
from bench.trace import Event, Trace

DATA = os.path.join(_paths.ROOT, "bench", "tests", "data")
DEV = "/device:TPU:0"


def _trace():
    # window 0..100; a module 10..40 holding a while 10..40 with a kernel
    # 12..30 and a fusion 30..38 inside; a second module 60..70
    modules = {DEV: [Event(10, 40, "jit_step"), Event(60, 70, "jit_x")]}
    ops = {DEV: [
        Event(10, 40, "%while.1 = (s32[]) while(...)"),
        Event(12, 30, "%zen_fused_sample.7 = s32[8,1] custom-call(s32[1] %a)"),
        Event(30, 38, "%fusion.2 = s32[8] fusion(s32[8] %b)"),
        Event(60, 70, "%pad.3 = s32[8] pad(s32[4] %c)"),
    ]}
    spans = [Event(0, 100, "bench.window"), Event(0, 50, "bench.step"),
             Event(50, 100, "bench.wait")]
    return Trace(modules, ops, spans)


def test_busy_idle_and_self_time():
    red = trace.reduce(_trace())
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(40e-9)
    assert red.idle_share == pytest.approx(0.6)
    ops = {trace.op_name(k): v for k, v in red.op_s.items()}
    assert ops["while.1"] == pytest.approx(4e-9)  # 30 less 18 + 8 nested
    assert ops["zen_fused_sample.7"] == pytest.approx(18e-9)
    assert red.kernel_s() == pytest.approx(18e-9)
    assert red.non_kernel_s() == pytest.approx(22e-9)
    assert red.kernel_s() + red.non_kernel_s() == pytest.approx(red.busy_s)


def test_gaps_named_by_the_innermost_host_span():
    red = trace.reduce(_trace())
    # gaps 70..100 and 40..60 (middle 50: bench.wait), 0..10 (bench.step)
    assert [g[0] for g in red.gaps] == ["bench.wait", "bench.wait",
                                        "bench.step"]
    assert [g[1] for g in red.gaps] == pytest.approx([30e-9, 20e-9, 10e-9])
    b = red.breakdown()
    assert b["device_ops"][0] == ["zen_fused_sample.7", pytest.approx(18e-9)]
    assert len(b["idle_gaps"]) == 3


def test_events_outside_the_window_are_left_out():
    t = _trace()
    t.spans[0] = Event(20, 65, "bench.window")
    red = trace.reduce(t)
    assert red.window_s == pytest.approx(45e-9)
    assert red.busy_s == pytest.approx(25e-9)  # 20..40 and 60..65


def test_recorded_chip_trace():
    red = trace.reduce(trace.load(os.path.join(DATA, "small.xplane.pb")))
    assert 0 < red.busy_s < red.window_s
    assert red.kernel_s() > 0
    assert red.kernel_s() + red.non_kernel_s() == pytest.approx(
        red.busy_s, rel=0.05)
    assert red.gaps and all(name.startswith("bench.")
                            for name, _ in red.gaps)
