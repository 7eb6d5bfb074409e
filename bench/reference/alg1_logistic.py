"""Plain reference of Algorithm 1 (arXiv 2408.12353, section 4) for the
logistic M-estimator, trusted center, DCQ aggregation with K levels.

Written from the paper and the protocol's stated wire layout, in
straightforward ``jax.numpy``; it imports nothing of the program. One
replicate maps ``(key, X, y, byz)`` to the quasi-Newton estimate
theta_qn. Data is ``(m+1, n, p)`` with machine 0 the center; ``byz``
marks Byzantine node machines (``(m,)``); the center is honest.

Randomness follows the protocol's wire layout: the replicate key splits
into 16; transmission r's Gaussian noise is ``normal(keys[2r'])`` of the
whole ``(m+1, p)`` transmission with ``r' = 0, 1, 3, 4, 5`` for R1..R5
(keys 4 and 5 belong to the untrusted center's variance round), scaled
per machine row. Noise scales are Theorem 4.5's, at eps/5 and delta/5
per transmission, with each machine's lambda_s calibrated from the
smallest eigenvalue of its local Hessian (floored at 1e-3).

Matrix products and factorisations run at ``precision``: ``"highest"``
is float32; ``"high"`` (the benchmark's control) takes three bfloat16
passes, ``hi*hi + hi*lo + lo*hi`` of each operand split into a bfloat16
high part and a bfloat16 remainder, written out so that it means the
same on every platform (the factorisations follow JAX's own ``"high"``,
which only a TPU honours).
"""
from __future__ import annotations

import math
from statistics import NormalDist

import jax
import jax.numpy as jnp


def noise_sds(p: int, n: int, eps: float, delta: float, gammas, tail: str,
              transmissions: int = 5):
    """Base noise s.d. of the five transmissions (Theorem 4.5 with unit
    lambda_s and norms), at the per-transmission budget."""
    eps_r, delta_r = eps / transmissions, delta / transmissions
    big_delta = math.sqrt(2.0 * math.log(1.0 / delta_r)) / eps_r
    t = math.log(n) if tail == "subexp" else math.sqrt(math.log(n))
    c = math.sqrt(p) * t * big_delta / n
    g = gammas
    return (2.02 * g[0] * c, 2.0 * g[1] * c, 2.02 * g[2] * c,
            2.0 * g[3] * c, 2.02 * g[4] * c)


def make_alg1(m: int, n: int, p: int, eps: float, delta: float,
              gammas=(2.0,) * 5, tail: str = "subexp", K: int = 10,
              newton_steps: int = 25, attack_factor: float = -3.0,
              precision: str = "highest"):
    """``replicate(key, X, y, byz) -> theta_qn`` for one replicate; run it
    under ``jax.default_matmul_precision(precision)`` so that the
    factorisations follow."""
    dt = jnp.dtype(jnp.float32)
    prec = jax.lax.Precision[precision.upper()]
    s1, s2, s3, s4, s5 = noise_sds(p, n, eps, delta, gammas, tail)
    nd = NormalDist()
    knots = [nd.inv_cdf(k / (K + 1.0)) for k in range(1, K + 1)]
    kappas = [k / (K + 1.0) for k in range(1, K + 1)]
    psi_sum = sum(math.exp(-0.5 * d * d) for d in knots) \
        / math.sqrt(2.0 * math.pi)
    ridge = 1e-9

    def c(x):
        return jnp.asarray(x, dt)

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(dt)
        return hi, (x - hi).astype(jnp.bfloat16).astype(dt)

    def dot(a, b):
        if precision == "highest":
            return jnp.matmul(a, b, precision=prec)
        (ah, al), (bh, bl) = split(a), split(b)
        return sum(jnp.matmul(x, y, precision=prec)
                   for x, y in ((ah, bh), (ah, bl), (al, bh)))

    solve, inv = jnp.linalg.solve, jnp.linalg.inv

    def sig(z):
        return 1.0 / (1.0 + jnp.exp(-z))

    def grad(th, X, y):
        return dot((sig(dot(X, th)) - y), X) / n

    def per_sample_grads(th, X, y):
        return (sig(dot(X, th)) - y)[:, None] * X

    def hess_w(th, X):
        s = sig(dot(X, th))
        return s * (1.0 - s)

    def hess(th, X):
        w = hess_w(th, X)
        return dot((X * w[:, None]).T, X) / n

    eye = jnp.eye(p, dtype=dt)

    def newton(X, y):
        def body(_, th):
            st = solve(hess(th, X) + ridge * eye, grad(th, X, y))
            nrm = jnp.sqrt(jnp.sum(st * st))
            st = jnp.where(nrm > 5.0, st * (5.0 / nrm), st)
            return th - st
        return jax.lax.fori_loop(0, newton_steps, body, jnp.zeros(p, dt))

    def median(v):
        return jnp.median(v, axis=0)

    def dcq(v, scale):
        med = median(v)
        total = jnp.zeros_like(med)
        for d, kap in zip(knots, kappas):
            ind = (v <= (med + scale * c(d))[None]).astype(dt)
            total = total + ind.sum(axis=0) - v.shape[0] * c(kap)
        return med - scale * total / c(v.shape[0] * psi_sum)

    def norm(v, axis=-1):
        return jnp.sqrt(jnp.sum(v * v, axis=axis))

    def replicate(key, X, y, byz):
        bad = jnp.concatenate([jnp.zeros((1,), bool), byz])[:, None]
        keys = jax.random.split(key, 16)

        def wire(k, vals, sd):
            z = jax.random.normal(k, vals.shape, jnp.float32)
            noisy = vals + c(sd).reshape(-1, 1) * z
            return jnp.where(bad, c(attack_factor) * noisy, noisy)

        Xc, yc = X[0], y[0]
        # R1: local M-estimators -> theta_cq
        th_loc = jax.vmap(newton)(X, y)
        lam = jax.vmap(lambda Xi, t: jnp.maximum(
            jnp.linalg.eigvalsh(hess(t, Xi))[0], 1e-3))(X, th_loc)
        s1j = c(s1) / lam
        th_dp = wire(keys[0], th_loc, s1j)
        th_med = median(th_dp)
        hinv = inv(hess(th_med, Xc) + ridge * eye)
        gs = per_sample_grads(th_med, Xc, yc)
        gc = gs - gs.mean(axis=0, keepdims=True)
        sig2 = jnp.diag(dot(dot(hinv, dot(gc.T, gc) / n), hinv))
        scale1 = jnp.sqrt(sig2 + n * s1j[0] ** 2) / math.sqrt(n)
        th_cq = dcq(th_dp, scale1)

        # R2: gradients at theta_cq -> g_cq
        g_dp = wire(keys[2], jax.vmap(lambda Xi, yi: grad(th_cq, Xi, yi))(
            X, y), jnp.full((m + 1,), s2))
        gvar = jnp.var(per_sample_grads(th_cq, Xc, yc), axis=0)
        scale2 = jnp.sqrt(jnp.maximum(gvar, 1e-12) + n * s2 ** 2) \
            / math.sqrt(n)
        g_cq = dcq(g_dp, scale2)

        # R3: Newton directions -> theta_os
        hinv_j = jax.vmap(lambda Xi: hess(th_cq, Xi) + ridge * eye)(X)
        dirs = jax.vmap(solve)(hinv_j, jnp.broadcast_to(g_cq, (m + 1, p)))
        s3j = (c(s3) / lam) * norm(dirs)
        d_dp = wire(keys[6], dirs, s3j)
        h0inv = inv(hess(th_cq, Xc) + ridge * eye)
        u = dot(h0inv, g_cq)
        t = (hess_w(th_cq, Xc) * dot(Xc, u))[:, None] * Xc
        hvar = jnp.var(dot(t, h0inv.T), axis=0)
        s30 = (c(s3) / lam[0]) * norm(dirs[0])
        scale3 = jnp.sqrt(jnp.maximum(hvar, 1e-12) + n * s30 ** 2) \
            / math.sqrt(n)
        th_os = th_cq - dcq(d_dp, scale3)

        # R4: gradient differences -> gdiff_cq, g_os
        step = th_os - th_cq
        gdiff = jax.vmap(lambda Xi, yi: grad(th_os, Xi, yi)
                         - grad(th_cq, Xi, yi))(X, y)
        s4e = c(s4) * norm(step)
        gd_dp = wire(keys[8], gdiff, jnp.full((m + 1,), s4e))
        gd = per_sample_grads(th_os, Xc, yc) - per_sample_grads(th_cq, Xc, yc)
        scale4 = jnp.sqrt(jnp.maximum(jnp.var(gd, axis=0), 1e-12)
                          + n * s4e ** 2) / math.sqrt(n)
        gdiff_cq = dcq(gd_dp, scale4)
        gosvar = jnp.var(per_sample_grads(th_os, Xc, yc), axis=0)
        scale4b = jnp.sqrt(jnp.maximum(gosvar, 1e-12) + n * s2 ** 2
                           + n * s4e ** 2) / math.sqrt(n)
        g_os = dcq(g_dp + gd_dp, scale4b)

        # R5: BFGS directions -> theta_qn
        rho = 1.0 / jnp.sum(step * gdiff_cq)

        def v_apply(x):                       # V x, V = I - rho y s^T
            return x - rho * gdiff_cq * jnp.sum(step * x)

        def vt_apply(x):                      # V^T x
            return x - rho * step * jnp.sum(gdiff_cq * x)

        h3 = jax.vmap(lambda h: vt_apply(solve(h, v_apply(g_os))))(hinv_j)
        s5j = c(s5) * norm(h3)
        h3_dp = wire(keys[10], h3, s5j)
        u = dot(h0inv, v_apply(g_os))
        t = (hess_w(th_cq, Xc) * dot(Xc, u))[:, None] * Xc
        t = jax.vmap(vt_apply)(dot(t, h0inv.T))
        h3var = jnp.var(t, axis=0)
        s50 = c(s5) * norm(h3[0])
        scale5 = jnp.sqrt(jnp.maximum(h3var, 1e-12) + n * s50 ** 2) \
            / math.sqrt(n)
        h2 = dcq(h3_dp, scale5) + rho * step * jnp.sum(step * g_os)
        return th_os - h2

    return replicate
