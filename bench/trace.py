"""From a profiler trace to the numbers per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` into plain
data, ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``, on one clock for host and device.
:func:`reduce` turns that into a :class:`Reduction`:

- the window: the host span ``bench.window`` that the harness opens
  around the measured work;
- device busy time: the union of the intervals in which an operation ran
  on a device, inside the window, averaged over the devices that ran any;
- device time per operation name, summed over devices and divided by
  their number;
- idle gaps of the first busy device, each charged to the innermost host
  event that covers its middle, on the host thread that opened the
  window.

Device events are named by the compiler with the whole HLO instruction
(``%ostat_pallas.515 = bf16[...] custom-call(...), ...``);
:func:`op_class` folds them into classes for the breakdown.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
#: the device line that holds one event per executed operation
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """Read an ``.xplane.pb`` into plain nested lists and dicts."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns),
                              float(e.duration_ns)] for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of closed intervals, sorted and disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_class(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``; a custom call is named by its
    target as well: ``custom-call:LuDecompositionBlock``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base = re.sub(r"\.\d+$", "", head)
    target = _TARGET.search(name)
    if target and target.group(1) != "tpu_custom_call":
        return f"{base}:{target.group(1)}"
    return base


def _device_ops(plane: dict) -> List[list]:
    lines = plane["lines"]
    named = [ln for ln in lines if ln["name"] == OPS_LINE]
    if named:
        return named[0]["events"]
    return max(lines, key=lambda ln: len(ln["events"]))["events"] \
        if lines else []


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # averaged over the devices with ops
    n_devices: int
    op_s: Dict[str, float]        # device seconds per op name, averaged
    op_count: Dict[str, int]      # events per op name, summed
    gaps: List[Tuple[str, float]]  # (host span, idle seconds), longest first
    spans: Dict[str, Tuple[int, float]]  # host span name -> (count, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, match) -> float:
        """Device seconds of the ops whose name ``match(name)`` accepts."""
        return sum(s for n, s in self.op_s.items() if match(n))

    def op_events(self, match) -> int:
        return sum(c for n, c in self.op_count.items() if match(n))

    def breakdown(self, top: int = 10) -> dict:
        by_class: Dict[str, float] = collections.defaultdict(float)
        for name, s in self.op_s.items():
            by_class[op_class(name)] += s
        ops = sorted(by_class.items(), key=lambda kv: -kv[1])[:top]
        by_span: Dict[str, float] = collections.defaultdict(float)
        for name, s in self.gaps:
            by_span[name] += s
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _host_events(trace: dict, window_span: str) -> List[list]:
    """The events of the host thread that opened the window."""
    for plane in trace["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                if any(e[0] == window_span for e in line["events"]):
                    return line["events"]
    raise ValueError(f"no host span {window_span!r} in the trace")


#: idle gaps charged one by one to a host event; shorter ones are summed
ATTRIBUTED_GAPS = 500


def _innermost(events: List[list], ts: Sequence[float]) -> List[str]:
    """For each time in ``ts``, the name of the shortest host event that
    covers it (``"(none)"`` where none does)."""
    import numpy as np
    if not events:
        return ["(none)"] * len(ts)
    start = np.array([e[1] for e in events])
    dur = np.array([e[2] for e in events])
    out = []
    for t in ts:
        cover = (start <= t) & (start + dur >= t)
        if cover.any():
            i = np.flatnonzero(cover)[np.argmin(dur[cover])]
            out.append(events[i][0])
        else:
            out.append("(none)")
    return out


def reduce(trace: dict, window_span: str = WINDOW_SPAN) -> Reduction:
    host = _host_events(trace, window_span)
    lo, hi = [(s, s + d) for n, s, d in host if n == window_span][0]
    spans: Dict[str, list] = {}
    for n, s, d in host:
        if s >= lo and s + d <= hi:
            c = spans.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += d * 1e-9
    busy, op_s, op_count = [], collections.defaultdict(float), \
        collections.Counter()
    first_busy: Optional[List[Interval]] = None
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        events = _device_ops(plane)
        ivs = []
        for name, s, d in events:
            iv = clip([(s, s + d)], lo, hi)
            if iv:
                ivs.append(iv[0])
                op_s[name] += (iv[0][1] - iv[0][0]) * 1e-9
                op_count[name] += 1
        if not ivs:
            continue
        merged = merge(ivs)
        busy.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged
    n_dev = len(busy)
    gaps: List[Tuple[str, float]] = []
    if first_busy is not None:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        idle = sorted(((b - a, a) for a, b in zip(edges[0::2], edges[1::2])
                       if b > a), reverse=True)
        named = idle[:ATTRIBUTED_GAPS]
        names = _innermost(host, [a + 0.5 * d for d, a in named])
        gaps = [(n, d * 1e-9) for n, (d, _) in zip(names, named)]
        rest = sum(d for d, _ in idle[ATTRIBUTED_GAPS:])
        if rest:
            gaps.append(("(shorter gaps)", rest * 1e-9))
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=(sum(busy) / n_dev) * 1e-9 if n_dev else 0.0,
        n_devices=n_dev,
        op_s={k: v / max(n_dev, 1) for k, v in op_s.items()},
        op_count=dict(op_count), gaps=gaps,
        spans={k: (v[0], v[1]) for k, v in spans.items()})
