"""Pure-jnp reference implementations of every registered aggregator.

This module is the numerical oracle for the Pallas kernel
(``repro.agg.kernel``) and the default backend off-TPU. It consolidates
what previously lived in ``core/robust_agg.py`` (mean / median / trimmed
mean / geometric median), ``core/dcq.py`` (the paper's DCQ estimator and
its efficiency theory) and ``kernels/dcq_ref.py`` (the MAD-scaled DCQ
oracle of the gradient-aggregation path).

All coordinate-wise rules take the machine axis as an ``axis`` argument
and operate with plain jnp reductions, so arbitrary leading/trailing dims
batch natively under (nested) vmap — that is their declared batching rule.

DCQ math (paper §3, eq. (3.1)/(4.4)): given m machine statistics
``Y_1..Y_m`` with sampling distribution ``mu + scale * Z``, ``Z ~ G``
(standard normal here),

    med  = med{Y_j}
    S    = sum_k sum_j [ I(Y_j <= med + scale*Delta_k) - kappa_k ]
    DCQ  = med - scale * S / (m * sum_k g(Delta_k))

with ``kappa_k = k/(K+1)`` and ``Delta_k = G^{-1}(kappa_k)``.

Asymptotics (Thm 3.1): sqrt(m)(DCQ - mu)/sigma_cq -> N(0,1) with
``sigma_cq^2 = D_K * scale^2``. NOTE: the paper's printed D_K omits the
``- kappa_{k1} kappa_{k2}`` centring term; the centred form (used in
Thm 4.3's V_{g,vr} and required to reproduce ARE 3/pi ~= 0.955) is

    D_K = sum_{k1,k2} [min(k1,k2)/(K+1) - k1*k2/(K+1)^2] / {sum_k psi(Delta_k)}^2.

We implement the centred form (see DESIGN.md §1).
"""
from __future__ import annotations

import functools
from statistics import NormalDist

import jax
import jax.numpy as jnp
from jax.scipy.stats import norm

#: MAD -> sd consistency factor for the normal reference distribution.
MAD_SIGMA = 1.4826
#: floor added to MAD scales so all-identical columns stay finite.
MAD_EPS = 1e-12


# ------------------------------------------------------- DCQ quantile theory

def quantile_levels(K: int) -> jnp.ndarray:
    """kappa_k = k/(K+1), k = 1..K."""
    return jnp.arange(1, K + 1, dtype=jnp.float64 if jax.config.jax_enable_x64
                      else jnp.float32) / (K + 1)


@functools.lru_cache(maxsize=None)
def normal_knots(K: int) -> tuple:
    """Delta_k = Psi^{-1}(k/(K+1)), k = 1..K, as Python floats computed on
    the host in double precision. The reference and the Pallas kernel both
    take their knots from here, so they compare values against the same
    thresholds (a device's single-precision ``ndtri`` can be off by
    hundreds of ulp in the tails, which moves whole indicators)."""
    nd = NormalDist()
    return tuple(nd.inv_cdf((k + 1.0) / (K + 1.0)) for k in range(K))


def quantile_knots(K: int) -> jnp.ndarray:
    """Delta_k = Psi^{-1}(kappa_k) for the standard-normal reference G."""
    return jnp.asarray(normal_knots(K), dtype=quantile_levels(K).dtype)


def d_k(K: int) -> float:
    """Variance inflation D_K of the DCQ estimator vs the mean (centred form).

    ARE(DCQ vs mean) = 1/D_K ; K -> inf gives D_K -> pi/3 (ARE 3/pi ~ 0.955).
    """
    kappa = quantile_levels(K)
    delta = quantile_knots(K)
    num = (jnp.minimum(kappa[:, None], kappa[None, :])
           - kappa[:, None] * kappa[None, :]).sum()
    den = norm.pdf(delta).sum() ** 2
    return float(num / den)


def are_dcq(K: int) -> float:
    """Asymptotic relative efficiency of DCQ vs the sample mean."""
    return 1.0 / d_k(K)


ARE_MEDIAN = 2.0 / jnp.pi  # ~0.637, quoted in the paper §1


# ----------------------------------------------------- simple aggregators

def mean_agg(values, axis: int = 0):
    return jnp.mean(values, axis=axis)


def median_agg(values, axis: int = 0):
    return jnp.median(values, axis=axis)


def trimmed_mean_agg(values, beta: float = 0.2, axis: int = 0):
    """Coordinate-wise beta-trimmed mean (Yin et al. 2018 convention): drop
    the floor(beta*m) smallest AND the floor(beta*m) largest entries per
    coordinate, keeping the central (1-2*beta) fraction. Robust to an
    alpha-fraction of Byzantine machines whenever beta >= alpha; on clean
    normal data ARE = 1 - 2*beta relative to the mean (so beta must be
    < 1/2)."""
    values = jnp.moveaxis(values, axis, 0)
    m = values.shape[0]
    g = max(int(beta * m), 0)
    srt = jnp.sort(values, axis=0)
    if 2 * g >= m:
        raise ValueError(f"trim fraction {beta} too large for m={m}")
    kept = srt[g:m - g]
    return kept.mean(axis=0)


def geometric_median_agg(values, axis: int = 0, iters: int = 50,
                         eps: float = 1e-8):
    """Weiszfeld iteration for the geometric median of m vectors. NOT
    coordinate-wise (the weights couple all coordinates), so its batching
    rule is an outer vmap, not the Pallas grid."""
    values = jnp.moveaxis(values, axis, 0)          # (m, ...)
    m = values.shape[0]
    flat = values.reshape(m, -1)

    def step(z, _):
        d = jnp.linalg.norm(flat - z[None], axis=1)
        w = 1.0 / jnp.maximum(d, eps)
        z_new = (w[:, None] * flat).sum(0) / w.sum()
        return z_new, None

    z0 = jnp.median(flat, axis=0)
    z, _ = jax.lax.scan(step, z0, None, length=iters)
    return z.reshape(values.shape[1:])


# --------------------------------------------------------------- DCQ rules

def dcq(values: jnp.ndarray, scale: jnp.ndarray, K: int = 10,
        axis: int = 0) -> jnp.ndarray:
    """Coordinate-wise DCQ estimate over the machine axis.

    Args:
      values: array with the machine axis at ``axis`` (e.g. (m, p)).
      scale: per-coordinate standard deviation of one machine's statistic
        (shape = values.shape without ``axis``). In the protocol this is
        ``sigma_hat_b / sqrt(n)`` etc. — the caller supplies the final scale.
      K: number of composite quantile levels.
      axis: machine axis.

    Returns: DCQ estimate, shape = values.shape without ``axis``.
    """
    values = jnp.moveaxis(values, axis, 0)
    m = values.shape[0]
    med = jnp.median(values, axis=0)
    delta = quantile_knots(K).astype(values.dtype)          # (K,)
    kappa = quantile_levels(K).astype(values.dtype)         # (K,)
    # thresholds: med + scale * Delta_k  -> (K, ...)
    thr = med[None] + scale[None] * delta.reshape((K,) + (1,) * med.ndim)
    ind = (values[None, :] <= thr[:, None]).astype(values.dtype)  # (K, m, ...)
    s = (ind - kappa.reshape((K,) + (1,) * values.ndim)).sum(axis=(0, 1))
    denom = m * norm.pdf(delta).sum().astype(values.dtype)
    return med - scale * s / denom


def dcq_with_sigma(values: jnp.ndarray, scale: jnp.ndarray, K: int = 10,
                   axis: int = 0):
    """DCQ estimate plus its asymptotic s.d. sigma_cq/sqrt(m) (Thm 3.1)."""
    est = dcq(values, scale, K=K, axis=axis)
    m = values.shape[axis]
    sd = jnp.sqrt(jnp.asarray(d_k(K), values.dtype)) * scale / jnp.sqrt(m)
    return est, sd


@functools.partial(jax.jit, static_argnames=("K", "axis"))
def dcq_jit(values, scale, K: int = 10, axis: int = 0):
    return dcq(values, scale, K=K, axis=axis)


def dcq_mad_reference(values: jnp.ndarray, K: int = 10,
                      axis: int = 0) -> jnp.ndarray:
    """MAD-scaled DCQ: median anchor, 1.4826*MAD scale, CQ correction.

    The gradient-aggregation variant (repro.dist.grad_agg): unlike the
    convex protocol there is no transmitted variance estimate, so the
    scale is calibrated from the data itself. Always computes in f32
    (matching the Pallas kernel) and returns f32.
    """
    values = jnp.moveaxis(values, axis, 0).astype(jnp.float32)
    med = jnp.median(values, axis=0)
    mad = jnp.median(jnp.abs(values - med[None]), axis=0)
    scale = MAD_SIGMA * mad + MAD_EPS
    return dcq(values, scale, K=K, axis=0)


def median_mad_dcq_reference(values: jnp.ndarray, K: int = 10,
                             axis: int = 0):
    """Fused single-pass statistics for the MAD-scaled gradient path:
    returns ``(median, raw MAD, MAD-scaled DCQ)`` in one call (the Pallas
    kernel computes all three from one resident tile)."""
    values = jnp.moveaxis(values, axis, 0).astype(jnp.float32)
    med = jnp.median(values, axis=0)
    mad = jnp.median(jnp.abs(values - med[None]), axis=0)
    scale = MAD_SIGMA * mad + MAD_EPS
    return med, mad, dcq(values, scale, K=K, axis=0)


def median_deviation_variance(values: jnp.ndarray, n, axis: int = 0,
                              floor: float = 1e-12) -> jnp.ndarray:
    """The untrusted-center variance estimate of Algorithm 1 (§4.3):
    ``max(median((v - median(v))^2) * n, floor)`` per coordinate — the
    robust plug-in the center uses when it cannot trust its own shard.
    One named implementation instead of the six ad-hoc ``jnp.median``
    spellings previously inlined in core/protocol.py."""
    values = jnp.moveaxis(values, axis, 0)
    med = jnp.median(values, axis=0)
    return jnp.maximum(jnp.median((values - med) ** 2, axis=0) * n, floor)
