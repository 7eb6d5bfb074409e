"""Time inside the program's spans and name scopes (``bench/scopes.py``):
innermost-scope assignment, unions, the window, the host-span
arithmetic, the xplane decoding and a reduced v5e trace."""
import json
import types
from pathlib import Path

import pytest

from bench import manifest, scopes, trace
from bench.run import load_module
from repro import spans

DATA = Path(__file__).parent / "data"
LOCAL, WIRE, CENTER = spans.ALG1_LOCAL, spans.ALG1_WIRE, spans.ALG1_CENTER
READERS = ("engine_trace_ms.mc", "engine_lower_ms.mc", "local_ms.mc",
           "wire_ms.mc", "center_ms.mc")


def _trace(device_events, paths, host_events):
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device_events}],
         "tf_op": paths},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": host_events}]}]}


def _run(calls):
    return types.SimpleNamespace(values={"calls": calls})


@pytest.mark.parametrize("path,scope", [
    ("jit(rounds)/vmap(alg1.local)/vmap()/dot_general:", LOCAL),
    ("jit(f)/alg1.wire/mul", WIRE),
    ("jit(f)/vmap(alg1.center)/jit(dcq)/vmap(alg1.local)/add:", LOCAL),
    ("jit(f)/alg1.center/transpose;jit(f)/alg1.local/reshape:", CENTER),
    ("jit(f)/reshape;jit(f)/vmap(alg1.wire)/add:", WIRE),
    ("jit(f)/alg1.localx/add", ""),
    ("jit(f)/vmap()/add:", ""),
    ("", ""),
])
def test_innermost_scope(path, scope):
    assert scopes.innermost(path, spans.ALG1_SCOPES) == scope


def test_while_and_body_counted_once_and_clipped_to_the_window():
    # window 100..1000; a while (local) 100..500 encloses its body's ops,
    # one center op straddles the window's start, one wire op its end,
    # one op carries no scope and one lies outside the window
    dev = [["%while.1", 100, 400], ["%fusion.1", 150, 100],
           ["%fusion.1", 300, 100], ["%ostat", 50, 100],
           ["%rng", 950, 200], ["%copy", 600, 100], ["%early", 0, 40]]
    paths = {"%while.1": "jit(f)/vmap(alg1.local)/while:",
             "%fusion.1": "jit(f)/vmap(alg1.local)/while/body/add:",
             "%ostat": "jit(f)/vmap(alg1.center)/ostat_pallas:",
             "%rng": "jit(f)/vmap(alg1.wire)/xor:",
             "%early": "jit(f)/vmap(alg1.wire)/xor:"}
    got = scopes.scope_seconds(
        _trace(dev, paths, [["bench.window", 100, 900]]), spans.ALG1_SCOPES)
    assert got[LOCAL] == pytest.approx(400e-9)
    assert got[CENTER] == pytest.approx(50e-9)
    assert got[WIRE] == pytest.approx(50e-9)


def test_scope_time_is_averaged_over_devices():
    t = _trace([["%a", 0, 100]], {"%a": "jit(f)/alg1.local/add"},
               [["bench.window", 0, 1000]])
    second = json.loads(json.dumps(t["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = [["%a", 0, 300]]
    t["planes"].append(second)
    got = scopes.scope_seconds(t, spans.ALG1_SCOPES)
    assert got[LOCAL] == pytest.approx(200e-9)


def test_no_scoped_op_reads_none(monkeypatch):
    t = _trace([["%a", 0, 100]], {}, [["bench.window", 0, 1000]])
    assert scopes.scope_seconds(t, spans.ALG1_SCOPES) == {}
    monkeypatch.setattr(scopes, "traced_scope_seconds",
                        lambda: scopes.scope_seconds(t, spans.ALG1_SCOPES))
    for read in (scopes.local_ms, scopes.wire_ms, scopes.center_ms):
        assert read(_run(3), None) is None


def test_a_scope_with_no_op_reads_zero_beside_one_that_has(monkeypatch):
    t = _trace([["%a", 0, 100]], {"%a": "jit(f)/alg1.local/add"},
               [["bench.window", 0, 1000]])
    monkeypatch.setattr(scopes, "traced_scope_seconds",
                        lambda: scopes.scope_seconds(t, spans.ALG1_SCOPES))
    assert scopes.local_ms(_run(2), None) == pytest.approx(100e-9 * 1e3 / 2)
    assert scopes.wire_ms(_run(2), None) == 0.0


def test_host_span_arithmetic():
    def red(**sp):
        return types.SimpleNamespace(spans=sp)
    both = red(**{spans.PROTOCOL_DISPATCH: (4, 6.0),
                  spans.PROTOCOL_TRACE: (4, 1.5)})
    assert scopes.engine_trace_ms(_run(4), both) == pytest.approx(375.0)
    assert scopes.engine_lower_ms(_run(4), both) == pytest.approx(1125.0)
    # dispatched and never traced: a cached engine
    cached = red(**{spans.PROTOCOL_DISPATCH: (4, 0.02)})
    assert scopes.engine_trace_ms(_run(4), cached) == 0.0
    assert scopes.engine_lower_ms(_run(4), cached) == pytest.approx(5.0)
    # a program without the spans, or a window without calls
    for r, rd in ((_run(4), red()), (_run(0), both)):
        assert scopes.engine_trace_ms(r, rd) is None
        assert scopes.engine_lower_ms(r, rd) is None


def test_a_program_without_spans_reads_none(monkeypatch):
    """A program from before ``repro.spans`` (the module cannot be
    imported) reads nothing, and no reader raises."""
    t = _trace([["%a", 0, 100]], {"%a": "jit(f)/alg1.local/add"},
               [["bench.window", 0, 1000]])
    monkeypatch.setattr(scopes, "spans", None)
    red = trace.reduce(t)
    for name in READERS:
        reader = load_module(manifest.metric_path(name), "m")
        assert reader.read(_run(2), red) is None, name


def test_traced_cpu_run_reports_the_host_span_metrics():
    """The Monte-Carlo cell traced on the CPU at a small size: the
    program's host spans are read; with no device plane the device
    scopes read nothing and are left out, as is every device metric."""
    import time
    from bench import run as harness
    from bench.tests.test_cells import small_spec
    res = harness.execute(small_spec("paper-logistic.mc-figeps"),
                          2**31 + 29, 1.0, True, time.perf_counter(),
                          require_tpu=False)
    got = res["metrics"]
    assert got["engine_trace_ms.mc"]["value"] > 0
    assert got["engine_lower_ms.mc"]["value"] > 0
    assert not {"local_ms.mc", "wire_ms.mc", "center_ms.mc"} & set(got)


# ---------------------------------------------------------------- xplane

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _map_entry(key, value):
    return _field(1, key) + _field(2, value)


def test_op_paths_reads_tf_op_from_event_metadata():
    """An XSpace written field by field: the device plane's event
    metadata carries ``tf_op`` as a string and as a reference to a stat
    metadata name; another stat and a host plane are ignored."""
    stat_meta = (_field(5, _map_entry(7, _field(1, 7) + _field(2, b"tf_op")))
                 + _field(5, _map_entry(8, _field(1, 8) + _field(2, b"flops")))
                 + _field(5, _map_entry(9, _field(1, 9) + _field(
                     2, b"jit(f)/alg1.wire/add:"))))
    ev_a = (_field(1, 1) + _field(2, b"%fusion.1 = f32[4] fusion()")
            + _field(5, _field(1, 8) + _field(3, 12))
            + _field(5, _field(1, 7) + _field(5, b"jit(f)/alg1.local/mul:")))
    ev_b = (_field(1, 2) + _field(2, b"%add.2 = f32[4] add()")
            + _field(5, _field(1, 7) + _field(7, 9)))
    ev_c = _field(1, 3) + _field(2, b"%copy.3 = f32[4] copy()")
    device = (_field(1, 5) + _field(2, b"/device:TPU:0")
              + _field(3, _field(2, b"XLA Ops"))
              + _field(4, _map_entry(1, ev_a)) + _field(4, _map_entry(2, ev_b))
              + _field(4, _map_entry(3, ev_c)) + stat_meta
              + _field(6, _field(1, 8) + _field(2, b"\x00" * 8)))
    host = (_field(2, b"/host:CPU")
            + _field(4, _map_entry(1, _field(2, b"bench.window"))))
    raw = _field(1, device) + _field(1, host) + _field(4, b"hostname")
    assert scopes.op_paths(raw) == {"/device:TPU:0": {
        "%fusion.1 = f32[4] fusion()": "jit(f)/alg1.local/mul:",
        "%add.2 = f32[4] add()": "jit(f)/alg1.wire/add:"}}


# ------------------------------------------------------- recorded on v5e

def test_recorded_v5e_trace_reads_all_five_metrics(monkeypatch):
    """One ``run_monte_carlo`` call of the Monte-Carlo cell traced on one
    TPU v5e chip, its window cut to the call (names of device events
    shortened to their instruction; ``tf_op`` as recorded)."""
    t = json.loads((DATA / "trace_mc_scopes_v5e.json").read_text())
    red = trace.reduce(t)
    monkeypatch.setattr(scopes, "traced_scope_seconds",
                        lambda: scopes.scope_seconds(t, spans.ALG1_SCOPES))
    run = _run(1)
    got = {}
    for name in READERS:
        reader = load_module(manifest.metric_path(name), "m")
        got[name] = reader.read(run, red)
        assert got[name] is not None and got[name] >= 0, name
    device_ms = load_module(manifest.metric_path("device_ms_per_call.mc"),
                            "m").read(run, red)
    layers = got["local_ms.mc"] + got["wire_ms.mc"] + got["center_ms.mc"]
    assert 0.9 * device_ms <= layers <= device_ms * (1 + 1e-9)
    assert got["local_ms.mc"] > got["center_ms.mc"] > got["wire_ms.mc"] > 0
    assert red.spans[spans.PROTOCOL_TRACE][0] == run.values["calls"]
    host_ms = 1e3 * red.window_s / run.values["calls"]
    assert 0 < got["engine_trace_ms.mc"] + got["engine_lower_ms.mc"] \
        <= host_ms
    # the kernel's ops all fall under the center
    plane = t["planes"][0]
    kernel = [n for n, _, _ in plane["lines"][0]["events"] if "ostat" in n]
    assert kernel and all(
        scopes.innermost(plane["tf_op"][n], spans.ALG1_SCOPES) == CENTER
        for n in kernel)
