"""The profiler-trace reduction (benchmark/trace.py): busy union, idle
share, per-module grouping, labelled idle gaps and the breakdown, on a
recorded CPU trace and on a hand-built TPU-shaped one."""

from pathlib import Path

import pytest

from benchmark import trace
from benchmark.trace import Event, Trace

FIXTURE = Path(__file__).parent / "fixtures" / "cpu_window.xplane.pb"


def test_union_merges_overlaps_and_clips():
    iv = [(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)]
    assert trace.union(iv, 1, 25) == [[1, 3], [5, 12], [20, 25]]
    assert trace.union([(0, 1)], 2, 3) == []


def _tpu_trace():
    dev = "/device:TPU:0"
    ops = [Event("fusion.1", 100, 50), Event("me_mc_kernel", 160, 40),
           Event("fusion.1", 300, 100), Event("copy", 390, 20),
           Event("fusion.1", 900, 200)]  # last one straddles the window end
    mods = [Event("jit__p_bits_step(7)", 100, 100),
            Event("jit__p_bits_step(7)", 300, 110),
            Event("jit_convert(2)", 900, 200)]
    host = [Event("bench.window", 0, 1000), Event("bench.capture", 200, 90),
            Event("bench.submit", 410, 500)]
    return Trace(ops={dev: ops}, modules={dev: mods}, host=host)


def test_reduce_tpu_shaped_trace_by_hand():
    red = trace.reduce(_tpu_trace(), top=3)
    # busy: [100,150) + [160,200) + [300,410) + [900,1000) = 300 ns
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(300e-9)
    assert red.idle_share == pytest.approx(0.70)
    assert red.module_s == pytest.approx(
        {"jit__p_bits_step(7)": 210e-9, "jit_convert(2)": 100e-9})
    assert red.op_s["fusion.1"] == pytest.approx(250e-9)
    assert red.kernel_calls == {"fusion.1": 3, "me_mc_kernel": 1, "copy": 1}
    # gaps, longest first and ties in time order: [410,900) submit,
    # [0,100) no span, [200,300) capture (90 of 100 ns)
    assert red.gaps == [("bench.submit", pytest.approx(490e-9)),
                        ("host", pytest.approx(100e-9)),
                        ("bench.capture", pytest.approx(100e-9))]
    b = trace.breakdown(red)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]
    assert len(b["idle_gaps"]) == 3


def test_reduce_without_window_or_device_ops():
    tr = _tpu_trace()
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(Trace(ops=tr.ops, modules=tr.modules, host=[]))
    red = trace.reduce(Trace(host=tr.host))
    assert red.busy_s == 0 and red.idle_share == 1 and red.op_s == {}


def test_tpu_op_names_are_the_instruction_names():
    assert trace._op_name("%cond.11 = (u32[131072]{0}, s32[1]) conditional(s32[] "
                          "%clamp.1, (pred[32400]) %tuple.1925)") == "cond.11"
    assert trace._op_name("fusion.3") == "fusion.3"


def test_recorded_cpu_trace():
    tr = trace.load(str(FIXTURE))
    assert sorted(tr.ops) == ["/device:CPU:0"]
    names = {h.name for h in tr.host}
    assert {"bench.window", "bench.capture", "bench.submit"} <= names
    red = trace.reduce(tr)
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1
    # the two jitted lambdas are XLA modules; their ops group under them
    assert set(red.module_s) == {"jit__lambda"}
    assert sum(red.module_s.values()) <= red.busy_s + 1e-12
    assert red.kernel_calls["dot_general.1"] == 3
    b = trace.breakdown(red)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) == 10
    # the longest idle gaps are the sleeps inside the capture spans
    assert b["idle_gaps"][0][0] == "bench.capture"
    assert "trace ops:" in trace.summary(tr, red)
