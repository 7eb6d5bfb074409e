"""``repro.agg`` — the unified robust-aggregation subsystem.

Every center-side aggregation in this repo routes through here: the
paper's Algorithm 1 rounds (core/protocol.py), the gradient aggregator
(dist/grad_agg.py), the SPMD collectives (dist/collectives.py), the
comparison baselines (core/baselines.py) and the sweep/benchmark layers.

Three pieces:

  * :mod:`repro.agg.registry`  — ``register("median"|"trimmed"|...)``;
    an :class:`Aggregator` bundles a jnp reference impl, a Pallas impl
    and a declared batching rule. Adding an aggregator is a one-file
    registry entry that is immediately dispatchable, sweepable and
    benchmarkable.
  * :mod:`repro.agg.reference` — the pure-jnp oracles (median, trimmed
    mean, geometric median, DCQ and its efficiency theory, MAD-scaled
    DCQ, the fused median+MAD+DCQ pass, the untrusted-center
    median-deviation variance).
  * :mod:`repro.agg.kernel`    — ONE generalized Pallas bisection
    order-statistics kernel computing k-th statistic / median / MAD /
    trimmed mean / DCQ from a shared rank-counting core, with leading
    batch axes mapped onto the grid.

Backend selection: ``backend=None`` ("auto") consults the MEASURED
dispatch table (:mod:`repro.agg.dispatch`): the autotuner
(:mod:`repro.agg.autotune`, ``repro-agg-tune``) times every backend per
(op, shape-bucket, platform) and records the winner plus its kernel
tuning parameters; auto dispatch looks the current shape's bucket up and
runs the recorded best. Unmeasured buckets fall back to the reference
oracle; platforms with no table at all fall back to the historical
heuristic (Pallas on TPU, reference elsewhere — off-TPU numbers stay
bit-identical to the historical sort-based path). ``backend="pallas"``
forces the kernel (interpret mode off-TPU); ``backend="reference"``
forces the oracle.

Migration note: ``core/robust_agg.py``, ``core/dcq.py``,
``kernels/dcq.py`` and ``kernels/dcq_ref.py`` are now thin shims over
this package; import from ``repro.agg`` directly in new code.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.agg import dispatch, kernel, masked, reference
from repro.agg.dispatch import DispatchTable
from repro.agg.kernel import OPS, cq_constants, dcq_pallas, ostat_pallas
from repro.agg.reference import (ARE_MEDIAN, are_dcq, d_k, dcq, dcq_jit,
                                 dcq_mad_reference, dcq_with_sigma,
                                 geometric_median_agg, mean_agg, median_agg,
                                 median_deviation_variance,
                                 median_mad_dcq_reference, quantile_knots,
                                 quantile_levels, trimmed_mean_agg)
from repro.agg.registry import (Aggregator, get_aggregator, has_masked,
                                has_pallas, register, registered)

__all__ = [
    "Aggregator", "register", "get_aggregator", "registered", "has_pallas",
    "has_masked", "dispatch", "DispatchTable",
    "aggregate", "aggregate_batched", "aggregate_masked", "median_mad_dcq",
    "median_deviation_variance",
    "ostat_pallas", "dcq_pallas", "OPS", "cq_constants",
    "dcq", "dcq_with_sigma", "dcq_jit", "dcq_mad_reference",
    "median_mad_dcq_reference", "quantile_levels", "quantile_knots",
    "d_k", "are_dcq", "ARE_MEDIAN",
    "mean_agg", "median_agg", "trimmed_mean_agg", "geometric_median_agg",
    "kernel", "masked", "reference",
]


# ----------------------------------------------------- built-in aggregators
#
# reference signature: (values, *, scale, K, trim_beta, axis) -> aggregate
# pallas signature:    (values, *, scale, K, trim_beta, tile, inner,
#                      n_bisect, interpret) with machine axis at -2,
#                      leading dims batch.

def _pallas_op(op):
    def run(values, *, scale=None, K=10, trim_beta=0.2, tile=512,
            inner=1, n_bisect=kernel.N_BISECT, interpret=None):
        return ostat_pallas(values, op, scale, K=K, trim_beta=trim_beta,
                            tile=tile, inner=inner, n_bisect=n_bisect,
                            interpret=interpret)
    return run


register(Aggregator(
    name="mean",
    reference=lambda values, *, scale=None, K=10, trim_beta=0.2, axis=0:
        reference.mean_agg(values, axis=axis),
    pallas=_pallas_op("mean"), masked=masked.masked_mean,
    doc="non-robust average (the efficiency yardstick)"))

register(Aggregator(
    name="median",
    reference=lambda values, *, scale=None, K=10, trim_beta=0.2, axis=0:
        reference.median_agg(values, axis=axis),
    pallas=_pallas_op("median"), masked=masked.masked_median,
    masked_bisect=masked.masked_median_bisect,
    doc="coordinate-wise median (Yin et al. 2018)"))

register(Aggregator(
    name="trimmed",
    reference=lambda values, *, scale=None, K=10, trim_beta=0.2, axis=0:
        reference.trimmed_mean_agg(values, beta=trim_beta, axis=axis),
    pallas=_pallas_op("trimmed"), masked=masked.masked_trimmed,
    doc="coordinate-wise beta-trimmed mean (Yin et al. 2018/19)"))

register(Aggregator(
    name="geomedian",
    reference=lambda values, *, scale=None, K=10, trim_beta=0.2, axis=0:
        reference.geometric_median_agg(values, axis=axis),
    pallas=None, batching="vmap", coordinatewise=False,
    masked=masked.masked_geomedian,
    doc="geometric median via Weiszfeld (Chen et al. 2017); couples "
        "coordinates, so no Pallas form and payload must stay replicated"))

register(Aggregator(
    name="dcq",
    reference=lambda values, *, scale=None, K=10, trim_beta=0.2, axis=0:
        reference.dcq(values, scale, K=K, axis=axis),
    pallas=_pallas_op("dcq"), needs_scale=True, masked=masked.masked_dcq,
    masked_bisect=masked.masked_dcq_bisect,
    doc="the paper's composite-quantile estimator with oracle scale "
        "(§3/§4.4)"))

register(Aggregator(
    name="dcq_mad",
    reference=lambda values, *, scale=None, K=10, trim_beta=0.2, axis=0:
        reference.dcq_mad_reference(values, K=K, axis=axis),
    pallas=_pallas_op("dcq_mad"), masked=masked.masked_dcq_mad,
    masked_bisect=masked.masked_dcq_mad_bisect,
    doc="MAD-self-calibrated DCQ (the gradient-aggregation path, no "
        "transmitted variance)"))


# ------------------------------------------------------------ dispatch API

def _pick_backend(agg: Aggregator, backend: Optional[str],
                  shape=None) -> "tuple[str, dict]":
    """Resolve the backend for one problem; returns (backend, params).

    ``backend=None`` with a known ``shape=(B, m, p)`` consults the
    measured dispatch table (repro.agg.dispatch); without a shape (or
    without a table for this platform) the historical platform heuristic
    applies. ``params`` are the table's tuned kernel knobs (tile / inner
    / n_bisect), empty for reference or forced backends.
    """
    params: dict = {}
    if backend is None:
        if agg.pallas is not None and shape is not None:
            dec = dispatch.decide(agg.name, *shape)
            backend, params = dec.backend, dict(dec.params)
        else:
            backend = "pallas" if jax.default_backend() == "tpu" \
                else "reference"
    if backend == "pallas" and agg.pallas is None:
        backend = "reference"       # e.g. geomedian: no kernel form
        params = {}
    if backend not in ("pallas", "reference"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend, params


def aggregate(values, method: str = "dcq", scale=None, K: int = 10,
              trim_beta: float = 0.2, axis: int = 0,
              backend: Optional[str] = None, interpret=None):
    """Aggregate ``values`` over its machine axis with a registered rule.

    The dispatch entry used by the protocol, the gradient aggregator and
    the baselines. ``backend=None`` consults the measured dispatch table
    for this (shape bucket, platform) — see :mod:`repro.agg.dispatch` —
    running the recorded best backend with its tuned kernel parameters;
    unmeasured shapes fall back to the reference oracle. Returns
    ``values.shape`` without ``axis``.
    """
    agg = get_aggregator(method)
    if agg.needs_scale and scale is None:
        raise ValueError(f"{method!r} needs a per-coordinate scale")
    vals = jnp.moveaxis(values, axis, 0)          # (m, *payload)
    payload = vals.shape[1:]
    p = 1
    for d in payload:
        p *= d
    be, params = _pick_backend(agg, backend, shape=(1, vals.shape[0], p))
    if be == "reference":
        # both backends return the values' dtype (a bf16 model leaf must
        # not come back f32 on one backend only)
        return agg.reference(values, scale=scale, K=K, trim_beta=trim_beta,
                             axis=axis).astype(values.dtype)
    # the kernel sees (rows, m, cols): leading payload dims go to its batch
    # grid. Flattening a 2-D payload into one coordinate axis instead makes
    # the TPU compiler build a relayout whose compile time and memory grow
    # with the leaf (minutes and GiBs for an embedding table).
    cols = payload[-1] if payload else 1
    kin = jnp.moveaxis(vals.reshape(vals.shape[0], -1, cols), 0, -2)
    sc = None
    if scale is not None:
        sc = jnp.broadcast_to(jnp.asarray(scale), payload).reshape(-1, cols)
    out = agg.pallas(kin, scale=sc, K=K, trim_beta=trim_beta,
                     interpret=interpret, **params)
    return out.reshape(payload).astype(values.dtype)


def aggregate_masked(values, fill, method: str = "dcq", scale=None,
                     K: int = 10, trim_beta: float = 0.2, axis: int = 0,
                     backend: Optional[str] = None):
    """Partial-fill aggregation over a fixed-capacity buffer: reduce the
    first ``fill`` rows of the machine axis, ignoring the stale tail.

    ``fill`` is a (traceable) scalar, never a shape — the serving step
    compiles ONCE per buffer capacity and every fill level reuses the
    executable. The result is byte-identical to calling this same entry
    on the dense unpadded ``values[:fill]`` batch (the fill-invariance
    contract, see :mod:`repro.agg.masked`).

    ``backend`` selects between the masked backends: ``"sort"`` (the
    contractual forms — the ``median`` rule is additionally bit-equal to
    the registry reference at every fill, sum-based rules match it up to
    float summation order) and ``"bisect"`` (sort-free rank counting,
    bisection resolution, the large-p serving path). ``backend=None``
    consults the measured dispatch table under op ``masked:<method>`` at
    trace time — the streaming service inherits the fastest measured
    backend per (capacity, p) bucket — and falls back to ``"sort"``.
    """
    agg = get_aggregator(method)
    if agg.masked is None:
        raise ValueError(f"{method!r} has no masked partial-fill form; "
                         f"servable rules: "
                         f"{[n for n in registered() if has_masked(n)]}")
    if agg.needs_scale and scale is None:
        raise ValueError(f"{method!r} needs a per-coordinate scale")
    vals = jnp.moveaxis(values, axis, 0)           # (capacity, *payload)
    payload = vals.shape[1:]
    p = 1
    for d in payload:
        p *= d
    if backend is None:
        dec = dispatch.decide(f"masked:{method}", 1, vals.shape[0], p)
        backend = dec.backend
        if backend == "bisect" and agg.masked_bisect is None:
            backend = "sort"
    if backend == "bisect":
        if agg.masked_bisect is None:
            servable = [n for n in registered()
                        if get_aggregator(n).masked_bisect is not None]
            raise ValueError(f"{method!r} has no sort-free masked form; "
                             f"bisect rules: {servable}")
        fn = agg.masked_bisect
    elif backend == "sort":
        fn = agg.masked
    else:
        raise ValueError(f"unknown masked backend {backend!r} "
                         "(one of 'sort', 'bisect')")
    flat = vals.reshape(vals.shape[0], -1) if payload else vals[:, None]
    sc = None
    if scale is not None:
        sc = jnp.broadcast_to(jnp.asarray(scale, vals.dtype),
                              payload).reshape(-1) if payload \
            else jnp.asarray(scale, vals.dtype).reshape(1)
    out = fn(flat, fill, scale=sc, K=K, trim_beta=trim_beta)
    return out.reshape(payload).astype(values.dtype)


def aggregate_batched(values, method: str = "dcq", scale=None, K: int = 10,
                      trim_beta: float = 0.2,
                      backend: Optional[str] = None, interpret=None):
    """Batched aggregation ``(*B, m, p) -> (*B, p)`` (machine axis at -2).

    This is each aggregator's declared batching rule made explicit: grid
    aggregators push the whole batch through ONE fused Pallas launch
    (leading axes on the grid); ``"vmap"`` aggregators (geomedian) batch
    via an outer vmap of the reference. On the reference backend the
    coordinate-wise rules batch natively via ``axis=-2`` reductions.
    """
    agg = get_aggregator(method)
    if agg.needs_scale and scale is None:
        raise ValueError(f"{method!r} needs a per-coordinate scale")
    if values.ndim < 2:
        raise ValueError(f"need (*batch, m, p), got {values.shape}")
    bn = 1
    for d in values.shape[:-2]:
        bn *= d
    be, params = _pick_backend(agg, backend,
                               shape=(bn,) + values.shape[-2:])
    if be == "pallas" and agg.batching == "grid":
        out = agg.pallas(values, scale=scale, K=K, trim_beta=trim_beta,
                         interpret=interpret, **params)
        return out.astype(values.dtype)
    if agg.batching == "vmap" and values.ndim > 2:
        inner = functools.partial(aggregate_batched, method=method,
                                  scale=scale, K=K, trim_beta=trim_beta,
                                  backend=backend, interpret=interpret)
        return jax.vmap(inner)(values)
    return agg.reference(values, scale=scale, K=K, trim_beta=trim_beta,
                         axis=-2).astype(values.dtype)


def median_mad_dcq(values, K: int = 10, backend: Optional[str] = None,
                   interpret=None):
    """Fused single-pass ``(median, raw MAD, MAD-scaled DCQ)`` over the
    machine axis at -2 (leading dims batch). The MAD-scaled gradient path
    uses all three: anchor, scale (robust variance = (1.4826*mad)^2) and
    the sharpened estimate — one resident tile instead of three passes.
    ``backend=None`` consults the dispatch table (op "median_mad_dcq")."""
    params: dict = {}
    if backend is None:
        if values.ndim >= 2:
            bn = 1
            for d in values.shape[:-2]:
                bn *= d
            dec = dispatch.decide("median_mad_dcq", bn,
                                  *values.shape[-2:])
            backend, params = dec.backend, dict(dec.params)
        else:
            backend = "pallas" if jax.default_backend() == "tpu" \
                else "reference"
    if backend not in ("pallas", "reference"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "pallas":
        return ostat_pallas(values, "median_mad_dcq", K=K,
                            interpret=interpret, **params)
    return reference.median_mad_dcq_reference(values, K=K, axis=-2)
