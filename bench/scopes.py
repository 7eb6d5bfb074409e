"""Time inside the program's own spans and device scopes (``repro.spans``).

Host spans: ``repro.protocol.dispatch`` around each engine call and
``repro.protocol.trace`` inside it while JAX traces the engine. They are
read from :class:`bench.trace.Reduction`'s ``spans``, the window's host
thread.

Device scopes: ``alg1.local``, ``alg1.wire`` and ``alg1.center`` are name
scopes, so the compiler writes them into each op's metadata
(``op_name``). On a TPU the profiler carries that path as the ``tf_op``
stat of the op's event metadata on the device plane (several paths, one
per fused op, joined by ``;``); ``jax.profiler.ProfileData`` does not
expose event metadata, so :func:`op_paths` reads those stats from the
xplane's protobuf wire format itself. :func:`load` gives the plain data
of :func:`bench.trace.load` with, on each device plane, ``"tf_op"``:
``{event name: path}``.

:func:`scope_seconds` assigns each ``XLA Ops`` event of the window to the
innermost of the scopes on its path and takes, per scope, the union of
its events' intervals (a ``while`` encloses its body's ops), averaged
over the devices that ran any. A program older than ``repro.spans``, or
an executable compiled without the scopes (one served from a persistent
cache written before them), reads nothing: the readers return ``None``.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, Iterator, List, Tuple

from bench import trace

try:
    from repro import spans
except ImportError:        # a program from before its spans and scopes
    spans = None


# ------------------------------------------------------ xplane wire format

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: ints for
    varints, bytes for length-delimited fields and fixed widths."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not in an xplane")
        yield key >> 3, val


def _map_values(entries: List[bytes]) -> Iterator[bytes]:
    """The values of a protobuf map field (entries are key 1, value 2)."""
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def _one(msg: bytes, number: int, default=b""):
    for f, v in _fields(msg):
        if f == number:
            return v
    return default


#: XSpace.planes; XPlane name, event_metadata and stat_metadata;
#: XEventMetadata name and stats; XStatMetadata name; XStat metadata_id,
#: str_value, bytes_value and ref_value (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES, _PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 1, 2, 4, 5
_EMETA_NAME, _EMETA_STATS, _SMETA_NAME = 2, 5, 2
_STAT_META_ID, _STAT_STR, _STAT_BYTES, _STAT_REF = 1, 5, 6, 7
#: the stat that carries an op's name-scope path on a TPU
OP_PATH_STAT = "tf_op"


def op_paths(raw: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, each op's event name -> its ``tf_op`` path."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(raw):
        if f != _SPACE_PLANES:
            continue
        parts: Dict[int, List[bytes]] = {}
        for g, v in _fields(plane):
            parts.setdefault(g, []).append(v)
        name = parts.get(_PLANE_NAME, [b""])[0].decode()
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for meta in _map_values(parts.get(_PLANE_STAT_META, [])):
            stat_names[_one(meta, 1, 0)] = _one(meta, _SMETA_NAME).decode()
        paths = {}
        for meta in _map_values(parts.get(_PLANE_EVENT_META, [])):
            ev_name, value = "", None
            for g, v in _fields(meta):
                if g == _EMETA_NAME:
                    ev_name = v.decode(errors="replace")
                elif g == _EMETA_STATS:
                    value = _path_value(v, stat_names) or value
            if value:
                paths[ev_name] = value
        out[name] = paths
    return out


def _path_value(stat: bytes, stat_names: Dict[int, str]):
    sid, value = 0, None
    for f, v in _fields(stat):
        if f == _STAT_META_ID:
            sid = v
        elif f in (_STAT_STR, _STAT_BYTES):
            value = v.decode(errors="replace")
        elif f == _STAT_REF:
            value = stat_names.get(v)
    return value if stat_names.get(sid) == OP_PATH_STAT else None


def load(path: str) -> dict:
    """:func:`bench.trace.load` of ``path``, each device plane with its
    ops' name-scope paths under ``"tf_op"``."""
    data = trace.load(path)
    with open(path, "rb") as fh:
        paths = op_paths(fh.read())
    for plane in data["planes"]:
        if plane["name"] in paths:
            plane["tf_op"] = paths[plane["name"]]
    return data


# ------------------------------------------------------------- reduction

def innermost(path: str, names) -> str:
    """The innermost of ``names`` on an op's path, or ``""``. A path is
    ``jit(f)/vmap(alg1.local)/...``: a scope is a segment, or wrapped by
    a transformation; fused ops give several paths joined by ``;``, and
    the first that holds a scope decides."""
    pattern = re.compile(r"(?:^|[/(])(%s)(?=[/):]|$)"
                         % "|".join(re.escape(n) for n in names))
    for one in path.split(";"):
        found = pattern.findall(one)
        if found:
            return found[-1]
    return ""


def scope_seconds(data: dict, names) -> Dict[str, float]:
    """Device seconds under each of ``names`` in the window: per scope
    the union of its ops' intervals, averaged over the devices that ran
    an op in the window. Empty when no op in the window carries a
    scope."""
    host = trace._host_events(data, trace.WINDOW_SPAN)
    lo, hi = [(s, s + d) for n, s, d in host if n == trace.WINDOW_SPAN][0]
    per_scope: Dict[str, float] = {}
    n_dev = 0
    for plane in data["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        paths = plane.get("tf_op", {})
        ivs: Dict[str, list] = {}
        ran = False
        for name, s, d in trace._device_ops(plane):
            iv = trace.clip([(s, s + d)], lo, hi)
            if not iv:
                continue
            ran = True
            scope = innermost(paths.get(name, ""), names)
            if scope:
                ivs.setdefault(scope, []).append(iv[0])
        n_dev += ran
        for scope, got in ivs.items():
            per_scope[scope] = per_scope.get(scope, 0.0) + 1e-9 * sum(
                b - a for a, b in trace.merge(got))
    return {k: v / n_dev for k, v in per_scope.items()}


@functools.lru_cache(maxsize=None)
def _window_scopes(path: str) -> Dict[str, float]:
    return scope_seconds(load(path), spans.ALG1_SCOPES)


def traced_scope_seconds() -> Dict[str, float]:
    """:func:`scope_seconds` of the traced run's profile, read once."""
    from bench.run import TRACE_DIR
    if spans is None:
        return {}
    try:
        path = trace.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return {}
    return _window_scopes(path)


# --------------------------------------------------------------- readers

def _calls(run):
    return run.values.get("calls") or None


def _scope_ms(run, const: str):
    """Device ms per call under the scope named by ``repro.spans.<const>``;
    ``None`` when no op of the window carries any of Algorithm 1's
    scopes."""
    got = traced_scope_seconds()
    calls = _calls(run)
    if not got or not calls:
        return None
    return 1e3 * got.get(getattr(spans, const), 0.0) / calls


def local_ms(run, red):
    return _scope_ms(run, "ALG1_LOCAL")


def wire_ms(run, red):
    return _scope_ms(run, "ALG1_WIRE")


def center_ms(run, red):
    return _scope_ms(run, "ALG1_CENTER")


def _span_s(red, name: str) -> Tuple[int, float]:
    return red.spans.get(name, (0, 0.0))


def engine_trace_ms(run, red):
    """Host ms per call spent tracing the engine: the seconds of the
    window's ``repro.protocol.trace`` spans over the calls. 0 where the
    engine was dispatched and never traced; ``None`` where the program
    has no dispatch span."""
    calls = _calls(run)
    if spans is None or not calls or \
            not _span_s(red, spans.PROTOCOL_DISPATCH)[0]:
        return None
    return 1e3 * _span_s(red, spans.PROTOCOL_TRACE)[1] / calls


def engine_lower_ms(run, red):
    """Host ms per call of an engine call besides its trace: the seconds
    of ``repro.protocol.dispatch`` less those of ``repro.protocol.trace``
    (lowering, persistent-cache fetch and launch), over the calls."""
    calls = _calls(run)
    if spans is None or not calls:
        return None
    n, dispatch_s = _span_s(red, spans.PROTOCOL_DISPATCH)
    if not n:
        return None
    return 1e3 * (dispatch_s - _span_s(red, spans.PROTOCOL_TRACE)[1]) / calls
