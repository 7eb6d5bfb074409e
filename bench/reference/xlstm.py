"""Plain reference of the xLSTM language model (arXiv:2405.04517) that
the benchmark trains: forward pass and mean next-token cross-entropy in
float32, for ``jax.grad``. It imports nothing of the program.

Parameters are a dict: ``embed`` (V, d), ``lm_head`` (d, V), ``norm_f``
(d,) and ``xlstm_layers``, a list of ``{"norm": (d,), "mixer": {...}}``.
Block i is an sLSTM block if ``i`` is in ``slstm_at``, else an mLSTM
block; each is pre-norm and residual: ``h + mixer(rms_norm(h))``.

mLSTM block: up-projection to ``di = up * d`` (``w_up``) with a SiLU gate
branch (``w_gate``); q, k, v from the up-projected stream (``w_q``,
``w_k``, ``w_v``, ``di x di``), H heads of ``di / H``; input and forget
gate pre-activations ``u @ w_if + b_if`` (first H columns input, last H
forget); the stabilised parallel form of the paper, over the whole
causal sequence at once:

    D[t, s] = F_t - F_s + i_s  (s <= t),  F = cumsum(log sigmoid(f))
    m_t = max_s D[t, s],  C = (q_t . k_s / sqrt(dh)) exp(D[t, s] - m_t)
    h_t = (sum_s C v_s) / max(|sum_s C|, exp(-m_t))

with the normaliser taken in log space (no overflow of ``exp(-m)`` for
strongly negative gates); out = (h * gate) @ w_down.

sLSTM block: per-head scalar memories with block-diagonal recurrence
``r_h`` (H, dh, 4 dh); the input projection ``x @ w_x + b`` and the
recurrent term are laid out head-major, ``(H, 4, dh)`` with the gates in
the order z, i, f, o; exponential input gate with the stabiliser
``m_t = max(log sigmoid(f) + m_{t-1}, i)``; ``h = o c / max(n, 1e-6)``;
out = h @ w_down. (The paper's sLSTM block adds a gated MLP after the
mixer; the configuration, like the program, has none: ``d_ff`` 0.)

Every matrix product runs at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(eq, *args):
    return jnp.einsum(eq, *args, precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def mlstm(p, x, n_heads):
    B, S, _ = x.shape
    di = p["w_up"].shape[1]
    dh = di // n_heads
    u = _mm("bsd,de->bse", x, p["w_up"])
    gate = jax.nn.silu(_mm("bsd,de->bse", x, p["w_gate"]))

    def heads(w):
        return _mm("bse,ef->bsf", u, w).reshape(B, S, n_heads, dh)
    q, k, v = heads(p["w_q"]), heads(p["w_k"]), heads(p["w_v"])
    g = _mm("bse,eg->bsg", u, p["w_if"]) + p["b_if"]
    i_pre, f_pre = g[..., :n_heads], g[..., n_heads:]
    F = jnp.cumsum(jax.nn.log_sigmoid(f_pre), axis=1)           # (B,S,H)
    D = F[:, :, None, :] - F[:, None, :, :] + i_pre[:, None, :, :]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    D = jnp.where(causal, D, -jnp.inf)                          # (B,t,s,H)
    m = D.max(axis=2)                                           # (B,t,H)
    C = _mm("bthd,bshd->btsh", q, k) / jnp.sqrt(float(dh)) \
        * jnp.exp(D - m[:, :, None, :])
    num = _mm("btsh,bshd->bthd", C, v)
    den = C.sum(axis=2)
    log_norm = jnp.maximum(jnp.log(jnp.maximum(jnp.abs(den), 1e-30)), -m)
    h = (num * jnp.exp(-log_norm)[..., None]).reshape(B, S, di)
    return _mm("bse,ed->bsd", h * gate, p["w_down"])


def slstm(p, x, n_heads):
    B, S, d = x.shape
    dh = d // n_heads
    wx = _mm("bsd,de->bse", x, p["w_x"]) + p["b"]               # (B,S,4d)

    def cell(st, wxt):
        c, n, h, m = st
        rh = _mm("bhd,hde->bhe", h, p["r_h"])
        pre = wxt.reshape(B, n_heads, 4, dh) + rh.reshape(B, n_heads, 4, dh)
        z = jnp.tanh(pre[:, :, 0])
        i_pre, f_pre = pre[:, :, 1], pre[:, :, 2]
        o = jax.nn.sigmoid(pre[:, :, 3])
        logf = jax.nn.log_sigmoid(f_pre)
        m_new = jnp.maximum(logf + m, i_pre)
        i_w = jnp.exp(i_pre - m_new)
        f_w = jnp.exp(logf + m - m_new)
        c = f_w * c + i_w * z
        n = f_w * n + i_w
        h = o * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    zeros = jnp.zeros((B, n_heads, dh), jnp.float32)
    st0 = (zeros, jnp.ones_like(zeros), zeros, zeros)
    _, hs = jax.lax.scan(cell, st0, jnp.moveaxis(wx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, d)
    return _mm("bse,ed->bsd", h, p["w_down"])


def loss(params, tokens, labels, cfg: dict):
    """Mean next-token cross-entropy of ``tokens`` (B, S) against
    ``labels`` (B, S); every parameter is taken in float32."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    h = p["embed"][tokens]
    for i, lp in enumerate(p["xlstm_layers"]):
        x = rms_norm(h, lp["norm"], cfg["norm_eps"])
        mixer = slstm if i in cfg["slstm_at"] else mlstm
        h = h + mixer(lp["mixer"], x, cfg["n_heads"])
    h = rms_norm(h, p["norm_f"], cfg["norm_eps"])
    logits = _mm("bsd,dv->bsv", h, p["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
