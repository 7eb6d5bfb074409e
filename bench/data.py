"""Inputs made from the seed, on the device, in one jitted call each.

The logistic design is the paper's (arXiv 2408.12353, section 5.1):
X ~ N(0, Sigma) with Toeplitz Sigma_ij = rho^|i-j|, theta* = p^-1/2
(1/2, ..., 1/2), Y ~ Bernoulli(sigmoid(X theta*)); machine 0 of the
(m+1, n, p) stack is the center.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def target_theta(p: int) -> jnp.ndarray:
    return jnp.full((p,), 0.5, jnp.float32) / jnp.sqrt(float(p))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def logistic_shards(key, machines: int, n: int, p: int, rho: float):
    """(machines+1, n, p) X and (machines+1, n) y, float32."""
    idx = jnp.arange(p)
    cov = rho ** jnp.abs(idx[:, None] - idx[None, :]).astype(jnp.float32)
    chol = jnp.linalg.cholesky(cov)
    kx, ky = jax.random.split(key)
    z = jax.random.normal(kx, (machines + 1, n, p), jnp.float32)
    X = jnp.einsum("mnp,qp->mnq", z, chol,
                   precision=jax.lax.Precision.HIGHEST)
    logits = jnp.einsum("mnp,p->mn", X, target_theta(p),
                        precision=jax.lax.Precision.HIGHEST)
    y = jax.random.bernoulli(ky, jax.nn.sigmoid(logits)).astype(jnp.float32)
    return X, y
