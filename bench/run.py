"""Run one benchmark cell once and print its result line.

    python3 -m bench --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's traffic file names the entry (``bench/entries/<entry>.py``)
that loads the program, warms up the cell's own shapes, measures for
``--seconds`` inside :meth:`Run.window` and checks what the timed path
produced against the plain reference. With ``--trace 1`` the window
runs under the JAX profiler and the cell's per-layer metrics are read
from the trace; with ``--trace 0`` its end-to-end metrics are printed.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``) and, last, ``checks``: each number compared with its
limit. The same checks are the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

from bench import manifest

ROOT = manifest.ROOT
#: JAX's persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run writes its profile (removed after it is read)
TRACE_DIR = ROOT / ".bench_trace"

EXIT_NO_DEVICE = 3
EXIT_SETUP = 4


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Counters:
    """JAX's own monitoring events, counted: traces, lowerings, backend
    compiles and persistent-cache hits. Program-independent."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax.monitoring as mon
        self.n: Dict[str, int] = {v: 0 for v in self.EVENTS.values()}
        self.n["cache_hits"] = 0
        self.n["cache_misses"] = 0

        def on_duration(event, duration, **kw):
            name = self.EVENTS.get(event)
            if name:
                self.n[name] += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.n["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.n["cache_misses"] += 1
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)


class Run:
    """What an entry drives: the cell's files, seed and clock, the host
    spans and counters it records, the measured window, the numbers it
    compares and the device's memory peak."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 t_process: float):
        self.cell = spec["cell"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_process = t_process
        self.spans: List[tuple] = []
        self.values: Dict[str, float] = {}   # counters and host numbers
        self.checks: List[dict] = []
        self.setup_s: Optional[float] = None
        self.window_bounds: Optional[tuple] = None
        self.memory_peak_bytes: Optional[int] = None
        self.counters = Counters()
        self.counts_at_window: Dict[str, Dict[str, int]] = {}

    # ------------------------------------------------------------ keys
    def key(self, stream: str):
        """An independent PRNG key for ``stream`` under ``--seed`` (any
        whole number below 2**64)."""
        import jax
        import zlib
        k = jax.random.PRNGKey(self.seed & 0xFFFFFFFF)
        k = jax.random.fold_in(k, (self.seed >> 32) & 0xFFFFFFFF)
        return jax.random.fold_in(k, zlib.crc32(stream.encode()))

    # ------------------------------------------------------- recording
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span, in the profiler's trace and in memory."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    @contextlib.contextmanager
    def window(self):
        """The measured window. Entering it ends set-up; under
        ``--trace 1`` the profiler records exactly this span."""
        import jax
        self.setup_s = time.perf_counter() - self.t_process
        self.counts_at_window["start"] = self.counters.snapshot()
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=opts)
        t0 = time.perf_counter()
        try:
            with self.span("bench.window"):
                yield
        finally:
            t1 = time.perf_counter()
            if self.trace:
                jax.profiler.stop_trace()
            self.window_bounds = (t0, t1)
            self.counts_at_window["end"] = self.counters.snapshot()

    @property
    def window_s(self) -> float:
        t0, t1 = self.window_bounds
        return t1 - t0

    def in_window(self) -> Dict[str, int]:
        """How often each JAX counter moved inside the window."""
        a, b = self.counts_at_window["start"], self.counts_at_window["end"]
        return {k: b[k] - a[k] for k in a}

    def read_memory_peak(self) -> None:
        """The device allocator's peak, read once the window has closed
        and before the reference runs (the peak never falls again)."""
        import jax
        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None

    def check(self, name: str, value: float, limit: float,
              better: str = "lower") -> bool:
        """Record one number compared with its limit; ``better`` says
        which side of the limit passes."""
        ok = math.isfinite(value) and (value <= limit if better == "lower"
                                       else value >= limit)
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "ok": bool(ok)})
        return ok

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def per_layer(run: Run, spec: dict, result: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell that its reader finds something
    to read for; a reader that finds nothing returns ``None`` and the
    metric is left out."""
    from bench import trace as tr
    red = tr.reduce(tr.load(tr.find_xplane(str(TRACE_DIR))))
    result["device"]["busy_s"] = red.busy_s
    result["device"]["window_s"] = red.window_s
    result["breakdown"] = red.breakdown()
    out = {}
    for m in spec["per_layer"]:
        reader = load_module(manifest.metric_path(m["name"]),
                             f"bench_metric_{m['name'].replace('.', '_')}")
        value = reader.read(run, red)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(spec: dict, seed: int, seconds: float, trace: bool,
            t_process: float, require_tpu: bool = True,
            entry_overrides: Optional[dict] = None) -> dict:
    """Drive one run of a resolved cell and return its result object.

    ``require_tpu=False`` and ``entry_overrides`` (keyword arguments the
    entry's ``run`` accepts, such as a planted fault) are for the
    benchmark's own tests on the CPU.
    """
    import jax
    n_chips = int(spec["cell"]["chips"])
    if require_tpu:
        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < n_chips:
            raise NoDevice(f"the cell needs {n_chips} TPU chip(s); JAX "
                           f"sees {len(devs)} {devs[0].platform} device(s)")
    run = Run(spec, seed, seconds, trace, t_process)
    entry = load_module(manifest.entry_path(spec["traffic"]["entry"]),
                        f"bench_entry_{spec['traffic']['entry']}")
    out = entry.run(run, **(entry_overrides or {}))
    result = {"correct": bool(out["correct"]) and all(
                  c["ok"] for c in run.checks),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {},
              "device": dict(_device_info(),
                             memory_peak_bytes=run.memory_peak_bytes)}
    if trace:
        result["metrics"] = per_layer(run, spec, result)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        e2e = dict(out["metrics"], setup_s=run.setup_s)
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {"value": float(e2e[m["name"]]),
                                            "unit": m["unit"]}
    result["checks"] = {c["name"]: {"value": _number(c["value"]),
                                    "limit": c["limit"]}
                        for c in run.checks}
    return result


def _number(x: float):
    """A float for JSON: non-finite values become their names."""
    return float(x) if math.isfinite(x) else repr(float(x))


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def _enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, t_process: Optional[float] = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(prog="python3 -m bench",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program under {src}; nothing run",
              file=sys.stderr)
        return EXIT_SETUP
    try:
        spec = manifest.resolve(manifest.load(ROOT), args.workload)
    except manifest.ManifestError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_SETUP
    sys.path.insert(0, str(src))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    _enable_cache()
    try:
        result = execute(spec, args.seed, args.seconds, bool(args.trace),
                         t_process)
    except NoDevice as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return EXIT_NO_DEVICE
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
