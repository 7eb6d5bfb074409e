"""xLSTM blocks: mLSTM (matrix memory, parallel/chunked) + sLSTM (scalar
memory, recurrent scan). [arXiv:2405.04517]

mLSTM uses the stabilised parallel form. Because the decay is separable —
D~[i,j] = F_i + (itilde_j - F_j) with F the cumulative log-forget — the
whole thing streams like flash attention: we scan KV chunks with a running
max and rescale, so no (S x S) matrix is live (needed for 4k train /
32k prefill). Decode is the O(1) matrix-memory recurrence with the
(C, n, m) stabiliser state.

sLSTM keeps per-head scalar memories with recurrent mixing; train runs a
lax.scan over time (inherently sequential, as in the paper).

Simplifications recorded in DESIGN.md §7: mLSTM block uses a pre
up-projection (factor 2) with a SiLU gate branch; sLSTM block is
norm -> mixer -> down-projection without a separate FFN (d_ff = 0).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import _init

Params = Dict[str, jnp.ndarray]

NEG_INF = -1e30
#: floor on the stabiliser m inside the normaliser max(|den|, exp(-m)):
#: exp(-m) overflows f32 below m = -88.7, and although the forward pass
#: then still gives h = num / inf = 0, the backward pass multiplies that
#: zero by exp(-m) = inf and every gradient upstream turns NaN (a strongly
#: negative input gate does it). Below the floor h < num * e^-80 ~ 0.
M_FLOOR = -80.0


# ================================================================== mLSTM

def mlstm_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    H = cfg.n_heads
    d_inner = 2 * d
    ks = jax.random.split(key, 7)
    return {
        "w_up": _init(ks[0], (d, d_inner), dtype=dtype),      # main branch
        "w_gate": _init(ks[1], (d, d_inner), dtype=dtype),    # SiLU gate
        "w_q": _init(ks[2], (d_inner, d_inner), dtype=dtype),
        "w_k": _init(ks[3], (d_inner, d_inner), dtype=dtype),
        "w_v": _init(ks[4], (d_inner, d_inner), dtype=dtype),
        "w_if": _init(ks[5], (d_inner, 2 * H), scale=0.02, dtype=jnp.float32),
        "b_if": jnp.concatenate([jnp.zeros((H,)),
                                 jnp.linspace(3.0, 6.0, H)]).astype(jnp.float32),
        "w_down": _init(ks[6], (d_inner, d), dtype=dtype),
    }


def _mlstm_qkvif(p: Params, x: jnp.ndarray, cfg: ModelConfig):
    H = cfg.n_heads
    B, S, _ = x.shape
    d_inner = p["w_up"].shape[1]
    dh = d_inner // H
    u = jnp.einsum("bsd,de->bse", x, p["w_up"])
    gate = jax.nn.silu(jnp.einsum("bsd,de->bse", x, p["w_gate"]))
    q = jnp.einsum("bse,ef->bsf", u, p["w_q"]).reshape(B, S, H, dh)
    k = jnp.einsum("bse,ef->bsf", u, p["w_k"]).reshape(B, S, H, dh)
    v = jnp.einsum("bse,ef->bsf", u, p["w_v"]).reshape(B, S, H, dh)
    gates = (jnp.einsum("bse,eg->bsg", u.astype(jnp.float32), p["w_if"])
             + p["b_if"])
    itilde, ftilde = gates[..., :H], gates[..., H:]           # (B,S,H)
    return q, k, v, itilde, ftilde, gate


def mlstm_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                  chunk: int = 512) -> jnp.ndarray:
    """Chunked-parallel stabilised mLSTM. x: (B, S, d_model)."""
    from repro.models import modes
    B, S, _ = x.shape
    H = cfg.n_heads
    d_inner = p["w_up"].shape[1]
    dh = d_inner // H
    q, k, v, itilde, ftilde, gate = _mlstm_qkvif(p, x, cfg)
    logf = jax.nn.log_sigmoid(ftilde)                         # (B,S,H)
    F = jnp.cumsum(logf, axis=1)                              # cumulative
    a = F                                                     # query weight
    b = itilde - F                                            # key weight

    Q = min(modes.chunk_override(chunk, S), S)
    pad = (-S) % Q
    if pad:
        def padf(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v = padf(q), padf(k), padf(v)
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=0.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)), constant_values=NEG_INF)
    Sp = q.shape[1]
    nc = Sp // Q

    def c(t):
        return jnp.moveaxis(t.reshape((B, nc, Q) + t.shape[2:]), 1, 0)

    qc, kc, vc, ac, bc = c(q), c(k), c(v), c(a), c(b)
    pos = jnp.moveaxis(jnp.arange(Sp).reshape(nc, Q), 0, 0)

    scale = 1.0 / (dh ** 0.5)

    def q_step(_, qi):
        q_i, a_i, pos_i = qi                                  # (B,Q,H,dh) ...

        def kv_step(carry, ki):
            num, den, m = carry
            k_j, v_j, b_j, pos_j = ki
            # decay matrix exponent: (B,Q,Q,H)
            dmat = a_i[:, :, None, :] + b_j[:, None, :, :]
            causal = pos_j[None, :] <= pos_i[:, None]         # (Q,Q)
            dmat = jnp.where(causal[None, :, :, None], dmat, NEG_INF)
            m_new = jnp.maximum(m, dmat.max(axis=2))          # (B,Q,H)
            w = jnp.exp(dmat - m_new[:, :, None, :])
            qk = jnp.einsum("bqhd,bkhd->bqkh", q_i, k_j).astype(jnp.float32) \
                * scale
            s = qk * w
            corr = jnp.exp(m - m_new)
            num_new = num * corr[..., None] + jnp.einsum(
                "bqkh,bkhd->bqhd", s, v_j.astype(jnp.float32))
            den_new = den * corr + s.sum(axis=2)
            return (num_new, den_new, m_new), None

        num0 = jnp.zeros((B, Q, H, dh), jnp.float32)
        den0 = jnp.zeros((B, Q, H), jnp.float32)
        m0 = jnp.full((B, Q, H), NEG_INF, jnp.float32)
        (num, den, m), _ = jax.lax.scan(kv_step, (num0, den0, m0),
                                        (kc, vc, bc, pos))
        h = num / jnp.maximum(jnp.abs(den),
                              jnp.exp(-jnp.maximum(m, M_FLOOR)))[..., None]
        return None, h

    _, h = jax.lax.scan(q_step, None, (qc, ac, pos))
    h = jnp.moveaxis(h, 0, 1).reshape(B, Sp, d_inner)[:, :S]
    out = h.astype(x.dtype) * gate
    return jnp.einsum("bse,ed->bsd", out, p["w_down"])


def mlstm_cache_init(cfg: ModelConfig, batch: int,
                     dtype=jnp.float32) -> Params:
    H = cfg.n_heads
    dh = 2 * cfg.d_model // H
    return {
        "C": jnp.zeros((batch, H, dh, dh), dtype),
        "n": jnp.zeros((batch, H, dh), dtype),
        "m": jnp.full((batch, H), NEG_INF, dtype),
        "f_acc": jnp.zeros((batch, H), dtype),   # running F (cum log forget)
    }


def mlstm_decode(p: Params, x: jnp.ndarray, cache: Params,
                 cfg: ModelConfig) -> Tuple[jnp.ndarray, Params]:
    """One-token recurrent mLSTM. x: (B, 1, d_model)."""
    B = x.shape[0]
    H = cfg.n_heads
    d_inner = p["w_up"].shape[1]
    dh = d_inner // H
    q, k, v, itilde, ftilde, gate = _mlstm_qkvif(p, x, cfg)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                       # (B,H,dh)
    itilde, ftilde = itilde[:, 0], ftilde[:, 0]               # (B,H)
    logf = jax.nn.log_sigmoid(ftilde)
    m_prev, C_prev, n_prev = cache["m"], cache["C"], cache["n"]
    m_new = jnp.maximum(logf + m_prev, itilde)
    fw = jnp.exp(logf + m_prev - m_new)
    iw = jnp.exp(itilde - m_new)
    C = fw[..., None, None] * C_prev + iw[..., None, None] * \
        jnp.einsum("bhd,bhe->bhde", k.astype(jnp.float32),
                   v.astype(jnp.float32))
    n = fw[..., None] * n_prev + iw[..., None] * k.astype(jnp.float32)
    scale = 1.0 / (dh ** 0.5)
    qf = q.astype(jnp.float32) * scale
    num = jnp.einsum("bhd,bhde->bhe", qf, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)),
                      jnp.exp(-jnp.maximum(m_new, M_FLOOR)))
    h = (num / den[..., None]).reshape(B, 1, d_inner)
    out = h.astype(x.dtype) * gate
    return (jnp.einsum("bse,ed->bsd", out, p["w_down"]),
            {"C": C, "n": n, "m": m_new,
             "f_acc": cache["f_acc"] + logf})


# ================================================================== sLSTM

def slstm_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    ks = jax.random.split(key, 3)
    return {
        "w_x": _init(ks[0], (d, 4 * d), dtype=dtype),         # z i f o
        # recurrent weights, block-diagonal per head: (H, dh, 4*dh)
        "r_h": _init(ks[1], (H, dh, 4 * dh), scale=0.1, dtype=jnp.float32),
        "b": jnp.concatenate([jnp.zeros((2 * d,)),
                              jnp.ones((d,)), jnp.zeros((d,))]
                             ).astype(jnp.float32),
        "w_down": _init(ks[2], (d, d), dtype=dtype),
    }


def slstm_cache_init(cfg: ModelConfig, batch: int,
                     dtype=jnp.float32) -> Params:
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    return {
        "c": jnp.zeros((batch, H, dh), dtype),
        "n": jnp.ones((batch, H, dh), dtype),
        "h": jnp.zeros((batch, H, dh), dtype),
        "m": jnp.zeros((batch, H, dh), dtype),
    }


def _slstm_cell(p: Params, xt: jnp.ndarray, state: Params, cfg: ModelConfig):
    """xt: (B, d) pre-projected input for one step."""
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    B = xt.shape[0]
    wx = jnp.einsum("bd,de->be", xt, p["w_x"]).astype(jnp.float32) + p["b"]
    rh = jnp.einsum("bhd,hde->bhe", state["h"], p["r_h"])     # (B,H,4dh)
    pre = wx.reshape(B, H, 4, dh) + rh.reshape(B, H, 4, dh)
    ztil, itil, ftil, otil = (pre[:, :, 0], pre[:, :, 1],
                              pre[:, :, 2], pre[:, :, 3])
    z = jnp.tanh(ztil)
    o = jax.nn.sigmoid(otil)
    logf = jax.nn.log_sigmoid(ftil)
    m_new = jnp.maximum(logf + state["m"], itil)
    iw = jnp.exp(itil - m_new)
    fw = jnp.exp(logf + state["m"] - m_new)
    c = fw * state["c"] + iw * z
    n = fw * state["n"] + iw
    h = o * c / jnp.maximum(n, 1e-6)
    return {"c": c, "n": n, "h": h, "m": m_new}


def slstm_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Sequential sLSTM over S (lax.scan). x: (B, S, d_model)."""
    B, S, d = x.shape
    state = slstm_cache_init(cfg, B)

    def step(st, xt):
        st2 = _slstm_cell(p, xt, st, cfg)
        return st2, st2["h"]

    _, hs = jax.lax.scan(step, state, jnp.moveaxis(x, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, d)
    return jnp.einsum("bse,ed->bsd", h.astype(x.dtype), p["w_down"])


def slstm_decode(p: Params, x: jnp.ndarray, cache: Params,
                 cfg: ModelConfig) -> Tuple[jnp.ndarray, Params]:
    st = _slstm_cell(p, x[:, 0], cache, cfg)
    B = x.shape[0]
    h = st["h"].reshape(B, 1, cfg.d_model)
    return jnp.einsum("bse,ed->bsd", h.astype(x.dtype), p["w_down"]), st
