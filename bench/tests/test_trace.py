"""The reduction from a profiler trace to busy time, kernel time and
idle gaps charged to host spans."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def _trace(device_events, host_events):
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Modules", "events": [["jit_step", 0, 1e9]]},
                   {"name": "XLA Ops", "events": device_events}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": host_events},
                   {"name": "other", "events": [["x", 0, 1e9]]}]}]}


def test_busy_idle_ops_and_gaps_on_a_small_trace():
    # window 100..1100 ns; ops overlap (200-400 and 300-500), one op
    # straddles the window's end
    dev = [["fusion.1", 200, 200], ["ostat_kernel", 300, 200],
           ["fusion.1", 700, 100], ["copy", 1050, 100], ["early", 0, 50]]
    host = [["bench.window", 100, 1000], ["bench.call", 120, 500],
            ["bench.call", 650, 400], ["compile", 660, 30]]
    red = trace.reduce(_trace(dev, host))
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx((300 + 100 + 50) * 1e-9)
    assert red.idle_share == pytest.approx(0.55)
    assert red.op_s["fusion.1"] == pytest.approx(300e-9)
    assert red.op_s["copy"] == pytest.approx(50e-9)
    assert "early" not in red.op_s
    assert red.op_seconds(lambda n: "ostat" in n) == pytest.approx(200e-9)
    assert red.op_count["fusion.1"] == 2
    # gaps: 100-200 (call), 500-700 (middle 600: first call ends 620),
    # 800-1050 (middle 925: second call)
    gaps = dict(red.breakdown()["idle_gaps"])
    assert gaps["bench.call"] == pytest.approx((100 + 200 + 250) * 1e-9)
    assert red.spans["bench.call"] == (2, pytest.approx(900e-9))
    assert red.breakdown()["device_ops"][0] == ["fusion", pytest.approx(300e-9)]


def test_busy_time_is_averaged_over_devices():
    t = _trace([["a", 100, 500]], [["bench.window", 0, 1000]])
    second = json.loads(json.dumps(t["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = [["a", 0, 1000]]
    t["planes"].append(second)
    red = trace.reduce(t)
    assert red.n_devices == 2
    assert red.busy_s == pytest.approx(750e-9)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(_trace([], [["bench.call", 0, 10]]))


def test_merge_and_clip():
    assert trace.merge([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert trace.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


@pytest.mark.parametrize("name,cls", [
    ('%ostat_pallas.515 = bf16[50304,1,1024]{2,1,0} custom-call(bf16[50304,'
     '4,1024] %pad.96), custom_call_target="tpu_custom_call"',
     "ostat_pallas"),
    ('%custom-call.43 = (f32[100,51,10,10]) custom-call(f32[100,51,10,10] '
     '%add_bitcast_fusion.1), custom_call_target="LuDecompositionBlock"',
     "custom-call:LuDecompositionBlock"),
    ("%fusion.12 = f32[4] fusion(f32[4] %p)", "fusion"),
    ("copy", "copy"),
])
def test_op_class(name, cls):
    assert trace.op_class(name) == cls


def _naive_busy(t, lo, hi, step=50.0):
    """Busy time by sampling the window on a grid: an independent check
    of the interval union."""
    import numpy as np
    ops = [e for p in t["planes"] if p["name"].startswith("/device:")
           for ln in p["lines"] if ln["name"] == "XLA Ops"
           for e in ln["events"]]
    grid = np.arange(lo, hi, step) + step / 2
    busy = np.zeros(grid.shape, bool)
    for _, s, d in ops:
        busy |= (grid >= s) & (grid < s + d)
    return busy.sum() * step * 1e-9


@pytest.mark.parametrize("name", ["trace_mc_v5e.json", "trace_qn_v5e.json"])
def test_recorded_v5e_trace(name):
    """The first milliseconds of a traced window recorded on one TPU v5e
    chip by ``python3 -m bench --trace 1``."""
    t = json.loads((DATA / name).read_text())
    red = trace.reduce(t)
    host = [e for p in t["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for e in ln["events"]]
    lo, dur = [(s, d) for n, s, d in host if n == "bench.window"][0]
    assert red.window_s == pytest.approx(dur * 1e-9)
    assert red.busy_s == pytest.approx(_naive_busy(t, lo, lo + dur),
                                       rel=1e-2, abs=2e-7)
    assert 0 < red.busy_s <= red.window_s
    gaps = sum(s for _, s in red.gaps)
    assert gaps == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    classes = {trace.op_class(n) for n in red.op_s}
    assert all(" = " not in c for c in classes)


def test_recorded_mc_trace_finds_the_kernel():
    """The excerpt of the Monte-Carlo cell starts just before the first
    kernel launch of its window."""
    from bench.readers import is_ostat
    red = trace.reduce(json.loads((DATA / "trace_mc_v5e.json").read_text()))
    assert red.op_events(is_ostat) >= 1
    assert 0 < red.op_seconds(is_ostat) <= red.busy_s
    assert any("ostat" in c for c, _ in red.breakdown()["device_ops"])
