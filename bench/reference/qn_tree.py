"""Plain reference of one quasi-Newton training step by Algorithm 1 over
a parameter tree: five transmissions, each corrupted on the Byzantine
machines' rows, aggregated per leaf by the MAD-scaled composite-quantile
rule, with per-machine L-BFGS memories. Noiseless (the cell trains
without DP). It imports nothing of the program.

Per step, with ``g_j(t)`` machine j's gradient of its own rows at t:

  R1  theta_j = theta - local_lr g_j(theta)       -> agg -> theta_cq
  R2  g_j(theta_cq)                               -> agg -> g_cq
  R3  L-BFGS direction of machine j's memory on g_cq -> agg -> H1;
      theta_os = theta_cq - lr H1,  s = theta_os - theta_cq
  R4  y_j = g_j(theta_os) - g_j(theta_cq)         -> agg -> y_cq
  R5  machine j pushes (s, y_j) where s.y_j > 1e-10; L-BFGS direction on
      g_os = g_cq + y_cq                          -> agg -> H2;
      theta_qn = theta_os - lr H2

Gradients and arithmetic are float32; every value the protocol stores
or transmits is held in its leaf's storage dtype (``store``), as the
configuration states. The control passes a ``store`` that rounds to the
precision below.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Callable, List

import jax
import jax.numpy as jnp

MAD_SIGMA = 1.4826
MAD_EPS = 1e-12


def tmap(fn, *trees):
    return jax.tree_util.tree_map(fn, *trees)


def tdot(a, b):
    return sum(jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def dcq_mad(v, K: int):
    """MAD-scaled DCQ over axis 0 of float32 ``v``."""
    nd = NormalDist()
    knots = [nd.inv_cdf(k / (K + 1.0)) for k in range(1, K + 1)]
    psi_sum = sum(math.exp(-0.5 * d * d) for d in knots) \
        / math.sqrt(2.0 * math.pi)
    m = v.shape[0]
    med = jnp.median(v, axis=0)
    scale = MAD_SIGMA * jnp.median(jnp.abs(v - med), axis=0) + MAD_EPS
    total = jnp.zeros_like(med)
    for k, d in enumerate(knots, start=1):
        total = total + (v <= med + scale * d).sum(axis=0) \
            - m * (k / (K + 1.0))
    return med - scale * total / (m * psi_sum)


def two_loop(s_hist, y_hist, count, g, hist: int):
    """L-BFGS two-loop recursion with Barzilai-Borwein scaling of the
    newest pair (1 while the memory is empty); slots older than
    ``count`` pushes are empty. Histories are lists of trees, oldest
    first."""
    valid = [i >= max(hist - count, 0) for i in range(hist)]
    q = tmap(lambda x: x.astype(jnp.float32), g)
    alphas = [None] * hist
    for i in reversed(range(hist)):
        if not valid[i]:
            continue
        rho = 1.0 / jnp.maximum(tdot(s_hist[i], y_hist[i]), 1e-12)
        alphas[i] = rho * tdot(s_hist[i], q)
        q = tmap(lambda qq, yy, a=alphas[i]: qq - a * yy.astype(jnp.float32),
                 q, y_hist[i])
    if count > 0:
        sy = tdot(s_hist[-1], y_hist[-1])
        yy = tdot(y_hist[-1], y_hist[-1])
        gamma = sy / jnp.maximum(yy, 1e-12)
    else:
        gamma = 1.0
    r = tmap(lambda x: gamma * x, q)
    for i in range(hist):
        if not valid[i]:
            continue
        rho = 1.0 / jnp.maximum(tdot(s_hist[i], y_hist[i]), 1e-12)
        b = rho * tdot(y_hist[i], r)
        r = tmap(lambda rr, ss, c=alphas[i] - b: rr + c * ss.astype(
            jnp.float32), r, s_hist[i])
    return r


class Memory:
    """One machine's L-BFGS history: ``hist`` slots, oldest first."""

    def __init__(self, like, hist: int):
        zero = tmap(jnp.zeros_like, like)
        self.s: List = [zero] * hist
        self.y: List = [zero] * hist
        self.count = 0
        self.hist = hist

    def push(self, s, y):
        self.s = self.s[1:] + [s]
        self.y = self.y[1:] + [y]
        self.count += 1


def make_step(grad_fn: Callable, machines: int, byzantine: int, cfg: dict,
              store: Callable):
    """``step(theta, memories, tokens, labels) -> (theta, mean loss,
    g_cq)``: one quasi-Newton step. ``grad_fn(theta, tokens, labels) ->
    (loss, grads)`` on one machine's rows; ``store(x, like)`` rounds a
    float32 value to the storage dtype of ``like``."""
    K, lr, local_lr = cfg["K"], cfg["lr"], cfg["local_lr"]

    @jax.jit
    def aggregate(stacked):
        def leaf(v):
            v32 = v.astype(jnp.float32)
            bad = (jnp.arange(machines) < byzantine).reshape(
                (-1,) + (1,) * (v.ndim - 1))
            return store(dcq_mad(jnp.where(bad, -v32, v32), K), v[0])
        return tmap(leaf, stacked)

    @jax.jit
    def axpy(c, x, y):                 # y + c x, stored like y
        return tmap(lambda xx, yy: store(yy.astype(jnp.float32)
                                         + c * xx.astype(jnp.float32), yy),
                    x, y)

    def stack(trees):
        return tmap(lambda *xs: jnp.stack(xs), *trees)

    compiled = {}

    def direction(mem: Memory, g, like):
        key = min(mem.count, mem.hist)
        if key not in compiled:
            compiled[key] = jax.jit(lambda sh, yh, gg, lk: tmap(
                store, two_loop(sh, yh, key, gg, mem.hist), lk))
        return compiled[key](mem.s, mem.y, g, like)

    def step(theta, memories, tokens, labels):
        rows = tokens.shape[0] // machines
        part = [(tokens[j * rows:(j + 1) * rows],
                 labels[j * rows:(j + 1) * rows]) for j in range(machines)]
        losses, local = [], []
        for t, lab in part:                                   # R1
            loss, g = grad_fn(theta, t, lab)
            losses.append(loss)
            local.append(axpy(-local_lr, g, theta))
        theta_cq = aggregate(stack(local))
        del local
        g_at_cq = [grad_fn(theta_cq, t, lab)[1] for t, lab in part]  # R2
        g_cq = aggregate(stack(g_at_cq))
        dirs = [direction(mm, g_cq, theta) for mm in memories]  # R3
        h1 = aggregate(stack(dirs))
        theta_os = axpy(-lr, h1, theta_cq)
        s = axpy(-1.0, theta_cq, theta_os)
        ys = [axpy(-1.0, gc, grad_fn(theta_os, t, lab)[1])     # R4
              for (t, lab), gc in zip(part, g_at_cq)]
        y_cq = aggregate(stack(ys))
        for mm, yj in zip(memories, ys):                       # R5
            if float(tdot(s, yj)) > 1e-10:
                mm.push(s, yj)
        g_os = axpy(1.0, y_cq, g_cq)
        dirs = [direction(mm, g_os, theta) for mm in memories]
        h2 = aggregate(stack(dirs))
        theta_qn = axpy(-lr, h2, theta_os)
        return theta_qn, float(jnp.mean(jnp.stack(losses))), g_cq

    return step
