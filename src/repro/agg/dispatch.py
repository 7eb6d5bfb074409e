"""Measured backend-dispatch table for ``repro.agg``.

``backend=None`` ("auto") used to mean a platform heuristic: Pallas on
TPU, jnp reference elsewhere. BENCH_agg showed that heuristic picking the
7x-slower path at the sweep regime — which backend is fastest depends on
the *shape* (the sorted reference wins at small p, rank-count bisection
at large p), not just the platform. This module replaces the heuristic
with measurement: the autotuner (:mod:`repro.agg.autotune`) times every
backend of every registered aggregator over a grid of ``(B, m, p)``
problems and records the winner — plus the winning kernel tuning
parameters (``tile``, ``inner``, ``n_bisect``) — into a versioned
on-disk JSON table, one file per platform.

Lookup is shape-bucketed: ``(B, m, p)`` maps to the key
``B<log2 B>:m<log2 m>:p<log2 p>`` (floor log2 per axis), so one measured
entry covers its whole power-of-two neighbourhood. Dispatch policy for
``backend=None``:

  * platform table present, bucket measured  -> the recorded best
    backend with its recorded kernel parameters;
  * platform table present, bucket UNmeasured -> the reference oracle
    (conservative: never ship an unmeasured kernel config);
  * no table for this platform at all        -> the historical platform
    heuristic (Pallas on TPU, reference elsewhere).

Masked (serving) rules dispatch through the same table under op keys
``masked:<rule>`` with backends ``sort`` (the contractual
:mod:`repro.agg.masked` forms) / ``bisect`` (the sort-free rank-count
forms); their unmeasured fallback is ``sort``.

A measured CPU default table is committed at ``tables/cpu.json``
(regenerate with ``repro-agg-tune``); ``REPRO_AGG_DISPATCH=<path>``
points dispatch at a re-tuned table without touching the package, and
:func:`set_table` injects one in-process (tests, notebooks).

All tuning parameters are **ints** end to end (``Decision.params`` is
validated on load): they flow into ``jax.jit`` static arguments, where a
float- or list-valued key would silently retrace per call — the exact
hazard ``repro.analyze``'s retrace-hazard rule exists to catch.
"""
from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax

SCHEMA = "repro.agg.dispatch/v1"

#: committed per-platform default tables (``cpu.json`` ships in the sdist)
TABLE_DIR = Path(__file__).resolve().parent / "tables"

#: environment override: path to a re-tuned table for this platform
ENV_VAR = "REPRO_AGG_DISPATCH"

#: kernel tuning parameters a table entry may carry (all static ints)
PARAM_KEYS = ("tile", "inner", "n_bisect")


@dataclasses.dataclass(frozen=True)
class Decision:
    """One dispatch outcome: which backend to run and how it was chosen.

    ``params`` are the measured kernel tuning ints (empty for reference /
    masked-sort); ``measured`` is False when the decision came from a
    fallback rather than a table entry; ``source`` says which
    ("table", "fallback-unmeasured", "fallback-no-table").
    """
    backend: str
    params: Dict[str, int]
    measured: bool
    source: str


def bucket_of(B: int, m: int, p: int) -> str:
    """Shape-bucket key: floor-log2 per axis, e.g. (320, 8, 10) ->
    ``"B8:m3:p3"``. One measured entry serves its whole power-of-two
    neighbourhood."""
    def lg(x):
        return max(int(x), 1).bit_length() - 1
    return f"B{lg(B)}:m{lg(m)}:p{lg(p)}"


def _fallback_backend(op: str, platform: str) -> str:
    if op.startswith("masked:"):
        return "sort"
    return "pallas" if platform == "tpu" else "reference"


class DispatchTable:
    """In-memory form of one platform's measured dispatch table."""

    def __init__(self, platform: str, entries: Optional[dict] = None,
                 meta: Optional[dict] = None):
        self.platform = platform
        self.entries: dict = entries if entries is not None else {}
        self.meta: dict = meta if meta is not None else {}

    # ------------------------------------------------------------ record

    def record(self, op: str, B: int, m: int, p: int, backend: str,
               time_s: float, **params) -> None:
        """Record one measured backend timing for a shape bucket. Tuning
        params must be ints (they become jit static arguments); the
        bucket's ``best`` backend is recomputed on every record."""
        bad = {k: v for k, v in params.items() if not isinstance(v, int)}
        if bad:
            raise TypeError(
                f"non-int tuning params {bad!r} for {op}: table params "
                "feed jit static arguments and must be hashable ints")
        key = f"{op}|{bucket_of(B, m, p)}"
        entry = self.entries.setdefault(key, {"backends": {}, "best": None})
        rec = {"time_s": float(time_s)}
        if params:
            rec["params"] = dict(params)
        entry["backends"][backend] = rec
        entry["best"] = min(entry["backends"],
                            key=lambda b: entry["backends"][b]["time_s"])

    # ------------------------------------------------------------ lookup

    def best(self, op: str, B: int, m: int,
             p: int) -> Optional[Tuple[str, Dict[str, int]]]:
        """The measured-best (backend, params) for this shape bucket, or
        None when the bucket was never measured for this op."""
        entry = self.entries.get(f"{op}|{bucket_of(B, m, p)}")
        if not entry or not entry.get("best"):
            return None
        backend = entry["best"]
        params = entry["backends"][backend].get("params", {})
        return backend, {k: int(v) for k, v in params.items()
                         if k in PARAM_KEYS}

    # ------------------------------------------------------- (de)serialize

    def to_json(self) -> dict:
        return {"schema": SCHEMA, "platform": self.platform,
                "meta": dict(self.meta),
                "entries": {k: self.entries[k]
                            for k in sorted(self.entries)}}

    @classmethod
    def from_json(cls, payload: dict) -> "DispatchTable":
        if payload.get("schema") != SCHEMA:
            raise ValueError(
                f"dispatch table schema {payload.get('schema')!r} != "
                f"{SCHEMA}; re-tune with repro-agg-tune")
        table = cls(payload["platform"], meta=dict(payload.get("meta", {})))
        for key, entry in payload.get("entries", {}).items():
            for backend, rec in entry.get("backends", {}).items():
                params = rec.get("params", {})
                bad = {k: v for k, v in params.items()
                       if not isinstance(v, int)}
                if bad:
                    raise ValueError(
                        f"dispatch entry {key!r}/{backend} carries non-int "
                        f"params {bad!r}: would retrace per call as a jit "
                        "static argument")
            table.entries[key] = {
                "backends": {b: dict(r)
                             for b, r in entry["backends"].items()},
                "best": entry.get("best")}
        return table

    def save(self, path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
            f.write("\n")
        return path

    @classmethod
    def load(cls, path) -> "DispatchTable":
        with open(path) as f:
            return cls.from_json(json.load(f))


# ------------------------------------------------------- module-level cache

#: platform -> DispatchTable | None (None = looked, no table on disk)
_CACHE: dict = {}
#: test/in-process injection: platform -> DispatchTable
_INJECTED: dict = {}
_generation = 0


def clear_cache() -> None:
    """Drop loaded tables (picks up a changed ENV_VAR / table file)."""
    global _generation
    _CACHE.clear()
    _generation += 1


def generation() -> int:
    """Changes with every :func:`clear_cache`, and so with every
    :func:`set_table`: a cache of what was traced under the tables
    (``repro.api``'s engines) keys its entries by it."""
    return _generation


def set_table(table: Optional[DispatchTable],
              platform: Optional[str] = None) -> None:
    """Inject a table for ``platform`` (default: the table's own platform)
    ahead of any on-disk file; ``set_table(None, platform)`` removes that
    injection and ``set_table(None)`` removes all of them. Test hook and
    notebook re-tuning hook."""
    if table is None:
        if platform is None:
            _INJECTED.clear()
        else:
            _INJECTED.pop(platform, None)
    else:
        _INJECTED[platform if platform is not None
                  else table.platform] = table
    clear_cache()


def load_table(platform: Optional[str] = None) -> Optional[DispatchTable]:
    """The active table for ``platform`` (default: current jax backend):
    injected > $REPRO_AGG_DISPATCH > committed tables/<platform>.json."""
    if platform is None:
        platform = jax.default_backend()
    if platform in _INJECTED:
        return _INJECTED[platform]
    if platform not in _CACHE:
        table = None
        env = os.environ.get(ENV_VAR)
        path = Path(env) if env else TABLE_DIR / f"{platform}.json"
        if path.is_file():
            table = DispatchTable.load(path)
            if table.platform != platform:
                table = None        # a cpu table must not steer a tpu run
        _CACHE[platform] = table
    return _CACHE[platform]


def decide(op: str, B: int, m: int, p: int,
           platform: Optional[str] = None) -> Decision:
    """Resolve ``backend=None`` for one aggregation problem (see module
    docstring for the policy)."""
    if platform is None:
        platform = jax.default_backend()
    table = load_table(platform)
    if table is None:
        return Decision(_fallback_backend(op, platform), {}, False,
                        "fallback-no-table")
    hit = table.best(op, B, m, p)
    if hit is None:
        backend = "sort" if op.startswith("masked:") else "reference"
        return Decision(backend, {}, False, "fallback-unmeasured")
    backend, params = hit
    return Decision(backend, params, True, "table")
