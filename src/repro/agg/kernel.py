"""Generalized batched Pallas order-statistics kernel (TPU VPU bisection).

One kernel serves every coordinate-wise aggregator in the registry: k-th
order statistic, median, trimmed mean, scale-supplied DCQ, MAD-scaled DCQ,
and a fused median+MAD+DCQ single pass — all built from the same bisection
rank-counting core. The GPU-natural formulation (per-coordinate sort) maps
poorly onto the TPU's vector unit — there is no fast per-lane sort.
Instead order statistics are found by binary-searching the value range per
coordinate, counting ranks with full-width VPU comparisons and reductions
over the machine axis; ``N_BISECT`` halvings pin the k-th order statistic
to below fp32 resolution. The whole tile lives in VMEM:

  values tile (m, TP)  ->  order stats / trimmed sums / CQ sums  ->  (TP,)

Grid: ``(batch, coordinate blocks)`` — LEADING BATCH AXES ARE MAPPED ONTO
THE PALLAS GRID, so the sweep engine's (scenarios, replicates, machines,
coords) stacks aggregate in one fused kernel launch instead of
per-scenario sorted fallbacks. The machine axis is small (m <= a few
thousand) and stays resident. All comparisons are masked-sum reductions —
no data-dependent control flow, MXU not needed (a pure VPU kernel, which
is why the paper's center-side aggregation is cheap on TPU).

Large-p regime: each grid program owns a block of ``tile * inner``
coordinates and walks it in an in-kernel coordinate-tile loop (``inner``
statically-unrolled subtiles of width ``tile``), so p in the
thousands–millions amortizes per-program grid overhead while
:func:`clamp_block` keeps the resident block under the VMEM budget —
the delivered block never exceeds ``VMEM_BUDGET_BYTES`` no matter how
large p grows (the grid covers the rest). ``tile``, ``inner`` and the
bisection trip count ``n_bisect`` are jit-static knobs tuned per
(op, shape-bucket, platform) by :mod:`repro.agg.autotune`; ``N_BISECT``
is only the untuned default (60 halvings pin fp32 exactly; measured
buckets typically need far fewer).

The trimmed mean needs no sort either: with the two bracketing order
statistics ``t_lo = v_(g)`` and ``t_hi = v_(m-1-g)`` in hand, the trimmed
sum is recovered exactly from masked sums with a tie correction:

  kept = [S(v<=t_hi) - (N(v<=t_hi) - (m-g)) t_hi]
       - [S(v<=t_lo) - (N(v<=t_lo) - g) t_lo]

Validated against repro.agg.reference (the pure-jnp oracle) over a
shape/dtype/m-parity sweep, including the batched grid path, in
tests/test_agg.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.agg.reference import MAD_EPS, MAD_SIGMA, normal_knots

#: default bisection trip count — enough halvings to pin any fp32 value;
#: the autotuner replaces this per bucket (32 already reaches fp32
#: resolution on unit-scale data).
N_BISECT = 60

#: per-program VMEM budget for the resident values block (bytes). A TPU
#: core has ~16 MB of VMEM; half of it leaves room for Pallas's
#: double-buffered pipelining of the next block plus outputs/scale.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: operations the generalized kernel computes from the shared bisection core
OPS = ("mean", "median", "kth", "trimmed", "dcq", "dcq_mad",
       "median_mad_dcq")


def clamp_block(m: int, p: int, tile: int, inner: int,
                budget: int = VMEM_BUDGET_BYTES):
    """Clamp a (tile, inner) candidate so one program's resident f32
    values block ``m x (tile * inner)`` fits the VMEM budget and carries
    no all-padding subtiles. Returns the adjusted (tile, inner)."""
    tile = max(128, min(tile, p)) if p >= 128 else max(1, min(tile, p))
    max_cols = max(budget // (4 * max(m, 1)), tile)
    inner = max(1, min(inner, max_cols // tile))
    # never a block wider than the (padded) coordinate count
    inner = min(inner, -(-p // tile))
    return tile, inner


def cq_constants(K: int):
    """Host-side composite-quantile constants: the K standard-normal knots
    ``Delta_k = Psi^{-1}(k/(K+1))`` (the reference's own,
    :func:`repro.agg.reference.normal_knots`) and ``sum_k psi(Delta_k)`` —
    Python floats baked into the kernel as compile-time scalars."""
    knots = normal_knots(K)
    psi_sum = sum(math.exp(-0.5 * d * d) for d in knots) \
        / math.sqrt(2.0 * math.pi)
    return knots, psi_sum


# ------------------------------------------------------ bisection core

def _kth_smallest(vals: jnp.ndarray, k, lo: jnp.ndarray,
                  hi: jnp.ndarray, n_bisect: int = N_BISECT) -> jnp.ndarray:
    """Bisection k-th order statistic (0-indexed) per column.

    vals: (m, tp) f32; k: scalar; lo/hi: (tp,) bracketing values.
    Returns (tp,) the k-th smallest per column (exact as a value present
    in the column up to the fixed ``n_bisect``-halving resolution — an
    early-exit-free trip count, tuned per shape bucket by the autotuner).
    """
    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        # rank of mid: how many values are <= mid
        cnt = jnp.sum((vals <= mid[None, :]).astype(jnp.float32), axis=0)
        go_right = cnt <= jnp.float32(k)          # need larger values
        lo = jnp.where(go_right, mid, lo)
        hi = jnp.where(go_right, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_bisect, body, (lo, hi))
    return hi     # converged upper bracket = smallest value with rank > k


def _kth_cols(vals: jnp.ndarray, k: int,
              n_bisect: int = N_BISECT) -> jnp.ndarray:
    lo = jnp.min(vals, axis=0)
    hi = jnp.max(vals, axis=0)
    return _kth_smallest(vals, k, lo, hi, n_bisect)


def _median_cols(vals: jnp.ndarray,
                 n_bisect: int = N_BISECT) -> jnp.ndarray:
    """Columnwise median via one or two bisection searches. vals: (m, tp)."""
    m = vals.shape[0]
    if m % 2 == 1:
        return _kth_cols(vals, (m - 1) // 2, n_bisect)
    return 0.5 * (_kth_cols(vals, m // 2 - 1, n_bisect)
                  + _kth_cols(vals, m // 2, n_bisect))


def _trimmed_cols(vals: jnp.ndarray, g: int,
                  n_bisect: int = N_BISECT) -> jnp.ndarray:
    """Columnwise beta-trimmed mean (g dropped per side) without sorting:
    bracket with two order statistics, recover the kept sum from masked
    sums with an exact tie correction."""
    m = vals.shape[0]
    if g == 0:
        return jnp.mean(vals, axis=0)
    t_lo = _kth_cols(vals, g, n_bisect)
    t_hi = _kth_cols(vals, m - 1 - g, n_bisect)
    le_hi = (vals <= t_hi[None, :]).astype(jnp.float32)
    le_lo = (vals <= t_lo[None, :]).astype(jnp.float32)
    top = (vals * le_hi).sum(axis=0) - (le_hi.sum(axis=0) - (m - g)) * t_hi
    bot = (vals * le_lo).sum(axis=0) - (le_lo.sum(axis=0) - g) * t_lo
    return (top - bot) / (m - 2 * g)


def _cq_correct(vals: jnp.ndarray, med: jnp.ndarray, scale: jnp.ndarray,
                knots, psi_sum: float) -> jnp.ndarray:
    """Composite-quantile correction: med - scale*S/(m*psi_sum) with
    S = sum_k sum_j [I(v_j <= med + scale*Delta_k) - kappa_k]."""
    m = vals.shape[0]
    K = len(knots)
    s = jnp.zeros_like(med)
    for j, delta in enumerate(knots):           # K static (10): unrolled
        thr = med + scale * delta
        kappa = (j + 1.0) / (K + 1.0)
        ind = (vals <= thr[None, :]).astype(jnp.float32)
        s = s + ind.sum(axis=0) - m * kappa
    return med - scale * s / (m * psi_sum)


# ---------------------------------------------------------- kernel body

def _ostat_kernel(*refs, op: str, knots, psi_sum: float, g: int, kth: int,
                  has_scale: bool, tile: int, inner: int, n_bisect: int):
    values_ref = refs[0]
    scale_ref = refs[1] if has_scale else None
    outs = refs[1 + int(has_scale):]

    # coordinate-tile double loop: the program's (m, tile*inner) block is
    # walked one statically-unrolled (m, tile) subtile at a time, so the
    # per-bisection working set stays one subtile wide while each grid
    # step amortizes over ``inner`` tiles.
    for j in range(inner):
        sl = slice(j * tile, (j + 1) * tile)
        vals = values_ref[0, :, sl].astype(jnp.float32)   # (m, tile)

        if op == "mean":
            res = (jnp.mean(vals, axis=0),)
        elif op == "kth":
            res = (_kth_cols(vals, kth, n_bisect),)
        elif op == "median":
            res = (_median_cols(vals, n_bisect),)
        elif op == "trimmed":
            res = (_trimmed_cols(vals, g, n_bisect),)
        elif op == "dcq":
            med = _median_cols(vals, n_bisect)
            scale = scale_ref[0, 0, sl].astype(jnp.float32)  # (tile,)
            res = (_cq_correct(vals, med, scale, knots, psi_sum),)
        elif op == "dcq_mad":
            med = _median_cols(vals, n_bisect)
            mad = _median_cols(jnp.abs(vals - med[None, :]), n_bisect)
            scale = MAD_SIGMA * mad + MAD_EPS
            res = (_cq_correct(vals, med, scale, knots, psi_sum),)
        elif op == "median_mad_dcq":
            # fused single pass: one resident subtile, three statistics out
            med = _median_cols(vals, n_bisect)
            mad = _median_cols(jnp.abs(vals - med[None, :]), n_bisect)
            scale = MAD_SIGMA * mad + MAD_EPS
            res = (med, mad, _cq_correct(vals, med, scale, knots, psi_sum))
        else:
            raise ValueError(f"unknown order-statistics op {op!r}")
        for out_ref, r in zip(outs, res):
            out_ref[0, 0, sl] = r.astype(out_ref.dtype)


# --------------------------------------------------------- public entry

@functools.partial(jax.jit, static_argnames=("op", "K", "trim_beta", "kth",
                                             "tile", "inner", "n_bisect",
                                             "interpret"))
def ostat_pallas(values: jnp.ndarray, op: str, scale=None, *, K: int = 10,
                 trim_beta: float = 0.2, kth: int = 0, tile: int = 512,
                 inner: int = 1, n_bisect: int = N_BISECT,
                 interpret=None):
    """Batched order-statistics aggregation ``(*B, m, p) -> (*B, p)``.

    The machine axis is second-to-last; any leading axes are batch and map
    onto the Pallas grid (one program per (batch row, coordinate block of
    ``tile * inner`` columns) — the block is walked in an in-kernel
    coordinate-tile loop and is clamped to the VMEM budget, so arbitrary
    p is safe). ``op="median_mad_dcq"`` returns the fused
    ``(median, mad, dcq)`` triple; every other op returns a single array.
    ``scale`` (``(*B, p)``) is required for ``op="dcq"``. ``tile``,
    ``inner`` and the bisection trip count ``n_bisect`` are the
    autotuner's knobs (repro.agg.autotune; dispatch feeds the measured
    values per shape bucket). ``interpret=None`` auto-selects interpret
    mode off-TPU (this container); on TPU the compiled kernel runs
    natively.
    """
    if op not in OPS:
        raise ValueError(f"unknown order-statistics op {op!r}; one of {OPS}")
    if values.ndim < 2:
        raise ValueError(f"need (*batch, m, p), got shape {values.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    batch = values.shape[:-2]
    m, p = values.shape[-2:]
    bn = 1
    for d in batch:
        bn *= d
    vals = values.reshape((bn, m, p))

    g = max(int(trim_beta * m), 0)
    if op == "trimmed" and 2 * g >= m:
        raise ValueError(f"trim fraction {trim_beta} too large for m={m}")
    knots, psi_sum = cq_constants(K)

    tile, inner = clamp_block(m, p, tile, inner)
    block = tile * inner
    pad = (-p) % block
    if pad:
        vals = jnp.pad(vals, ((0, 0), (0, 0), (0, pad)))
    pp = p + pad

    # per-coordinate operands and outputs are (bn, 1, pp): the TPU lowering
    # refuses a (1, block) block of a (bn, pp) array for bn > 1, since the
    # second-minor block dim must be a multiple of 8 or the whole dim
    has_scale = op == "dcq"
    operands = [vals]
    in_specs = [pl.BlockSpec((1, m, block), lambda b, i: (b, 0, i))]
    if has_scale:
        if scale is None:
            raise ValueError("op='dcq' needs a per-coordinate scale")
        sc = jnp.broadcast_to(scale, batch + (p,)).reshape((bn, 1, p))
        if pad:
            sc = jnp.pad(sc, ((0, 0), (0, 0), (0, pad)),
                         constant_values=1.0)
        operands.append(sc)
        in_specs.append(pl.BlockSpec((1, 1, block), lambda b, i: (b, 0, i)))

    n_out = 3 if op == "median_mad_dcq" else 1
    out_spec = pl.BlockSpec((1, 1, block), lambda b, i: (b, 0, i))
    out_shape = [jax.ShapeDtypeStruct((bn, 1, pp), values.dtype)
                 for _ in range(n_out)]
    outs = pl.pallas_call(
        functools.partial(_ostat_kernel, op=op, knots=knots,
                          psi_sum=psi_sum, g=g, kth=kth,
                          has_scale=has_scale, tile=tile, inner=inner,
                          n_bisect=n_bisect),
        grid=(bn, pp // block),
        in_specs=in_specs,
        out_specs=[out_spec] * n_out,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    outs = tuple(o[:, 0, :p].reshape(batch + (p,)) for o in outs)
    return outs if n_out > 1 else outs[0]


@functools.partial(jax.jit, static_argnames=("K", "tile", "interpret"))
def dcq_pallas(values: jnp.ndarray, K: int = 10, tile: int = 512,
               interpret=None) -> jnp.ndarray:
    """DCQ-with-MAD aggregation of (m, p) -> (p,) via the Pallas kernel.

    Back-compat entry (formerly kernels/dcq.py): ``interpret=None``
    auto-selects like ``ostat_pallas`` — interpret mode off-TPU, native
    on TPU (the old hardcoded ``interpret=True`` default silently ran a
    TPU caller in interpret mode).
    """
    return ostat_pallas(values, "dcq_mad", K=K, tile=tile,
                        interpret=interpret)
