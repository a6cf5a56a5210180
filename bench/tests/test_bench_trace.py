"""The reduction from a profiler trace to busy time, kernel time, collective
time and idle gaps: by hand on a made-up trace, and on a small trace recorded
on the chip."""

from __future__ import annotations

import types
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "data" / "qwen15-l8.qsgd4.n2.t256.xplane.pb"


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur), stats=stats)


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name,
        lines=[types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


KERNEL = ('%branch_0_fun.8 = (s8[2,64,1024]{2,1,0}, f32[2,64,1]{2,1,0}) '
          'custom-call(s32[2]{0} %copy-done.98, f32[2,64,1024]{2,1,0} %fusion.1), '
          'custom_call_target="tpu_custom_call"')


def made_up():
    """Window [100, 1100) ns. Device: a fusion [100, 300), a Pallas kernel
    [300, 500), an all-reduce [450, 700) half hidden under the kernel, idle
    [700, 1000) while the host waits, a fusion [1000, 1100)."""
    dev = _plane("/device:TPU:0", {"XLA Ops": [
        _ev("fusion.1", 100, 200),
        _ev(KERNEL, 300, 200),
        _ev("all-reduce.3", 450, 250),
        _ev("fusion.2", 1000, 100),
        _ev("fusion.9", 1200, 50),          # after the window
    ]})
    host = _plane("/host:CPU", {"python": [
        _ev("bench.window", 100, 1000),
        _ev("bench.dispatch", 650, 100),
        _ev("bench.wait", 750, 300),
    ]})
    return types.SimpleNamespace(planes=[host, dev])


def test_reduction_by_hand():
    r = trace.reduce_profile(made_up())
    assert r.window_ns == 1000 and r.devices == 1
    assert r.busy_ns == 700                      # [100, 700) and [1000, 1100)
    assert r.kernel_ns == {"qsgd_block_workers": 200}
    assert r.kernel_count == {"qsgd_block_workers": 1}
    assert r.collective_ns == 250
    assert r.collective_exposed_ns == 200        # [500, 700)
    assert r.gaps == [("bench.wait", 300)]       # [700, 1000), mostly waiting
    b = trace.breakdown(r)
    assert b["device_ops"][0] == ["all-reduce.3", 250e-9]
    assert b["idle_gaps"] == [["bench.wait", 300e-9]]


def test_a_trace_without_the_window_span_is_refused():
    pd = made_up()
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace.reduce_profile(pd)


def test_recorded_chip_trace():
    r = trace.load(str(FIXTURE))
    assert r.devices == 1
    assert 0 < r.busy_ns <= r.window_ns
    assert r.kernel_ns and all(v > 0 for v in r.kernel_ns.values())
    assert sum(r.kernel_ns.values()) < r.busy_ns
    assert r.collective_ns == 0
    b = trace.breakdown(r)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
