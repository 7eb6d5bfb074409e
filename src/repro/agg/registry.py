"""Aggregator registry: one entry per robust center-side aggregation rule.

Every step of the paper's Algorithm 1 — and of the Yin-style distributed
Newton / Byzantine-robust one-step baselines it compares against — is a
coordinate-wise aggregation over a leading machine axis. This registry is
the single place those rules live. An :class:`Aggregator` bundles

  * ``reference`` — the pure-jnp implementation (the numerical oracle and
    the default backend off-TPU; machine axis is an ``axis`` argument, so
    arbitrary leading/trailing dims batch natively under vmap);
  * ``pallas``    — the Pallas order-statistics kernel entry
    (``repro.agg.kernel.ostat_pallas`` partial), or ``None`` when the rule
    has no kernel form (geomedian couples coordinates via Weiszfeld);
  * ``batching``  — the declared batching rule: ``"grid"`` means extra
    leading axes map onto the Pallas grid (coordinate-wise rules),
    ``"vmap"`` means batch via an outer vmap of the reference.

Registering a new aggregator makes it immediately dispatchable from
``repro.agg.aggregate``, sweepable (``Scenario.aggregator`` validates
against this registry) and benchmarkable (``benchmarks/kernel_bench.py``
iterates the registry).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Aggregator:
    """One robust aggregation rule over the machine axis.

    ``reference(values, *, scale, K, trim_beta, axis)`` -> aggregate with
    the machine axis removed; ``pallas(values, *, scale, K, trim_beta,
    tile, interpret)`` expects the machine axis at ``-2`` (payload last,
    any leading dims are batch) and returns ``values.shape`` without the
    machine axis.
    """
    name: str
    reference: Callable
    pallas: Optional[Callable] = None
    #: "grid"  — coordinate-wise; leading batch axes ride the Pallas grid.
    #: "vmap"  — not coordinate-wise; batch via outer vmap of reference.
    batching: str = "grid"
    #: ``masked(values, fill, *, scale, K, trim_beta)`` — partial-fill form
    #: over a fixed-capacity ``(C, p)`` buffer whose first ``fill`` (traced)
    #: rows are valid; byte-identical to itself on the dense unpadded batch
    #: (repro.agg.masked). ``None`` = rule not servable from a ring buffer.
    masked: Optional[Callable] = None
    #: sort-free masked form (rank-count bisection, repro.agg.masked
    #: ``*_bisect``): same signature and fill-invariance contract as
    #: ``masked`` but O(n_bisect * C * p) comparisons instead of a
    #: per-column sort — the large-p serving backend. The dispatch table
    #: (repro.agg.dispatch, op key ``masked:<name>``) picks between the
    #: two per measured shape bucket. ``None`` = no bisect form.
    masked_bisect: Optional[Callable] = None
    #: True when the rule consumes a per-coordinate scale (protocol DCQ).
    needs_scale: bool = False
    #: coordinate-wise rules commute with payload sharding (collectives.py)
    coordinatewise: bool = True
    doc: str = ""


_REGISTRY: Dict[str, Aggregator] = {}
_generation = 0


def register(agg: Aggregator) -> Aggregator:
    """Register (or replace) an aggregator under ``agg.name``."""
    global _generation
    if agg.batching not in ("grid", "vmap"):
        raise ValueError(f"unknown batching rule {agg.batching!r}")
    _REGISTRY[agg.name] = agg
    _generation += 1
    return agg


def generation() -> int:
    """Changes with every :func:`register`: a cache of what was traced
    from the registry (``repro.api``'s engines) keys its entries by it."""
    return _generation


def get_aggregator(name: str) -> Aggregator:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown aggregator {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def registered() -> Tuple[str, ...]:
    """Names of all registered aggregators, sorted."""
    return tuple(sorted(_REGISTRY))


def has_pallas(name: str) -> bool:
    return get_aggregator(name).pallas is not None


def has_masked(name: str) -> bool:
    return get_aggregator(name).masked is not None
