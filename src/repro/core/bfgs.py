"""BFGS machinery (paper §4.1 and eq. 4.13) + L-BFGS two-loop.

The protocol's second iteration updates every machine's inverse Hessian by

    H^+ = V^T H V + rho * s s^T,      V = I - rho * y s^T,
    rho = 1 / (s^T y),   s = theta_os - theta_cq,   y = g_diff,

and only ever needs matrix-vector products with V — we exploit the rank-1
structure (``VOp``) so the center never materialises a p x p matrix
(DESIGN.md hardware-adaptation note).

Memory budget at model scale: the dense p x p inverse stays confined to
the convex head (``bfgs_inverse_update``).  For the pytree engine the
curvature state is an ``LBFGSMemory`` of ``hist`` (s, y) PAIRS — leaves
shaped ``(hist, *leaf)`` — so quasi-Newton state costs ``2 * hist``
parameter copies (hist=5 -> 10 copies) instead of p^2 floats; the
two-loop recursion (``lbfgs_two_loop_tree``) applies the implied inverse
Hessian with tree-wide inner products and never materialises a matrix.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.transport import tree_dot


@dataclasses.dataclass(frozen=True)
class VOp:
    """V = I - rho * y s^T applied in O(p)."""
    s: jnp.ndarray
    y: jnp.ndarray
    rho: jnp.ndarray

    def __call__(self, x: jnp.ndarray, transpose: bool = False) -> jnp.ndarray:
        if transpose:   # V^T x = x - rho * s (y . x)
            return x - self.rho * self.s * jnp.dot(self.y, x)
        return x - self.rho * self.y * jnp.dot(self.s, x)


def make_v(s: jnp.ndarray, y: jnp.ndarray) -> VOp:
    rho = 1.0 / jnp.dot(s, y)
    return VOp(s=s, y=y, rho=rho)


def bfgs_inverse_update(h_inv: jnp.ndarray, s: jnp.ndarray,
                        y: jnp.ndarray) -> jnp.ndarray:
    """Dense BFGS inverse update (eq. 4.13), used on the p x p convex head."""
    v = make_v(s, y)
    rho = v.rho
    # V^T H V computed with two rank-1 applications: cost O(p^2)
    hv = h_inv - jnp.outer(h_inv @ v.y, v.s) * rho          # H V
    vthv = hv - jnp.outer(v.s, v.y @ hv) * rho              # V^T (H V)
    return vthv + rho * jnp.outer(s, s)


def bfgs_dir_product(h_inv_apply: Callable[[jnp.ndarray], jnp.ndarray],
                     v: VOp, g: jnp.ndarray,
                     rho_term: bool = True) -> jnp.ndarray:
    """h = V^T H^{-1} V g (+ rho s s^T g): the machine-side product in (4.15)
    plus the center-side rank-1 term. ``h_inv_apply`` is any linear operator
    (dense solve for the convex head, L-BFGS two-loop at NN scale)."""
    out = v(g, transpose=False)
    out = h_inv_apply(out)
    out = v(out, transpose=True)
    if rho_term:
        out = out + v.rho * v.s * jnp.dot(v.s, g)
    return out


# ------------------------------------------------------------- L-BFGS

@dataclasses.dataclass
class LBFGSMemory:
    """Fixed-size (s, y) history for two-loop products at NN scale.

    ``s_hist``/``y_hist`` are either flat ``(hist, p)`` arrays (the
    historical convex path) or pytrees with ``(hist, *leaf)`` leaves (the
    model-zoo path) — the flat form IS the single-leaf special case.  A
    leading machine axis may sit in front of ``hist`` when per-machine
    memories are carried under ``jax.vmap``.
    """
    s_hist: Any              # (hist, p) array or pytree of (hist, *leaf)
    y_hist: Any
    count: jnp.ndarray       # scalar int

    @staticmethod
    def init(hist: int, p: int, dtype=jnp.float32) -> "LBFGSMemory":
        return LBFGSMemory(jnp.zeros((hist, p), dtype),
                           jnp.zeros((hist, p), dtype),
                           jnp.zeros((), jnp.int32))

    @staticmethod
    def init_like(hist: int, tree: Any,
                  machines: Optional[int] = None) -> "LBFGSMemory":
        """Zeroed history shaped after ``tree``; with ``machines=m`` the
        leaves get a leading machine axis ``(m, hist, *leaf)`` (and
        ``count`` becomes ``(m,)``) for per-machine memories that a
        ``jax.vmap`` over machines strips back down."""
        lead = (machines, hist) if machines else (hist,)

        def zeros(p):
            return jnp.zeros(lead + tuple(p.shape), p.dtype)
        count = jnp.zeros((machines,) if machines else (), jnp.int32)
        return LBFGSMemory(jax.tree_util.tree_map(zeros, tree),
                           jax.tree_util.tree_map(zeros, tree), count)

    def push(self, s: Any, y: Any) -> "LBFGSMemory":
        def roll(hist, v):
            return jnp.roll(hist, -1, axis=0).at[-1].set(v)
        s_hist = jax.tree_util.tree_map(roll, self.s_hist, s)
        y_hist = jax.tree_util.tree_map(roll, self.y_hist, y)
        return LBFGSMemory(s_hist, y_hist, self.count + 1)


jax.tree_util.register_pytree_node(
    LBFGSMemory,
    lambda mem: ((mem.s_hist, mem.y_hist, mem.count), None),
    lambda _, kids: LBFGSMemory(*kids),
)


def lbfgs_two_loop(mem: LBFGSMemory, g: jnp.ndarray,
                   gamma: float = 1.0) -> jnp.ndarray:
    """Standard two-loop recursion; empty slots are masked out."""
    hist = mem.s_hist.shape[0]
    valid = jnp.arange(hist) >= jnp.maximum(hist - mem.count, 0)

    def bwd(carry, inp):
        q = carry
        s, y, ok = inp
        rho = jnp.where(ok, 1.0 / jnp.maximum(jnp.dot(s, y), 1e-12), 0.0)
        a = rho * jnp.dot(s, q)
        return q - jnp.where(ok, a, 0.0) * y, a

    q, alphas = jax.lax.scan(bwd, g, (mem.s_hist, mem.y_hist, valid),
                             reverse=True)
    r = gamma * q

    def fwd(carry, inp):
        r = carry
        s, y, ok, a = inp
        rho = jnp.where(ok, 1.0 / jnp.maximum(jnp.dot(s, y), 1e-12), 0.0)
        b = rho * jnp.dot(y, r)
        return r + jnp.where(ok, a - b, 0.0) * s, None

    r, _ = jax.lax.scan(fwd, r, (mem.s_hist, mem.y_hist, valid, alphas))
    return r


def lbfgs_two_loop_tree(mem: LBFGSMemory, g: Any, gamma=1.0) -> Any:
    """Two-loop recursion over an arbitrary gradient pytree.

    ``jax.lax.scan`` slices every history leaf along its ``hist`` axis, so
    each step sees one (s, y) pytree pair; curvatures are tree-wide inner
    products. On a single flat leaf this computes exactly what
    ``lbfgs_two_loop`` computes (asserted in tests/test_protocol_pytree.py).
    """
    hist_leaves = jax.tree_util.tree_leaves(mem.s_hist)
    hist = hist_leaves[0].shape[0]
    valid = jnp.arange(hist) >= jnp.maximum(hist - mem.count, 0)

    def bwd(q, inp):
        s, y, ok = inp
        rho = jnp.where(ok, 1.0 / jnp.maximum(tree_dot(s, y), 1e-12), 0.0)
        a = rho * tree_dot(s, q)
        coef = jnp.where(ok, a, 0.0)
        # curvature scalars (and gamma) are f32; each leaf keeps its own
        # dtype, so a mixed bf16/f32 parameter tree is a valid scan carry
        # and the step returns parameters of the dtypes it was given
        q = jax.tree_util.tree_map(
            lambda qq, yy: (qq - coef * yy).astype(qq.dtype), q, y)
        return q, a

    q, alphas = jax.lax.scan(bwd, g, (mem.s_hist, mem.y_hist, valid),
                             reverse=True)
    r = jax.tree_util.tree_map(lambda x: (gamma * x).astype(x.dtype), q)

    def fwd(r, inp):
        s, y, ok, a = inp
        rho = jnp.where(ok, 1.0 / jnp.maximum(tree_dot(s, y), 1e-12), 0.0)
        b = rho * tree_dot(y, r)
        coef = jnp.where(ok, a - b, 0.0)
        r = jax.tree_util.tree_map(
            lambda rr, ss: (rr + coef * ss).astype(rr.dtype), r, s)
        return r, None

    r, _ = jax.lax.scan(fwd, r, (mem.s_hist, mem.y_hist, valid, alphas))
    return r


def lbfgs_gamma(mem: LBFGSMemory) -> jnp.ndarray:
    """Barzilai–Borwein initial scaling gamma = s.y / y.y of the most
    recent pair; 1.0 while the memory is empty."""
    s_last = jax.tree_util.tree_map(lambda h: h[-1], mem.s_hist)
    y_last = jax.tree_util.tree_map(lambda h: h[-1], mem.y_hist)
    sy = tree_dot(s_last, y_last)
    yy = tree_dot(y_last, y_last)
    return jnp.where(mem.count > 0,
                     sy / jnp.maximum(yy, 1e-12), 1.0).astype(jnp.float32)
