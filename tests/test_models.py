"""Model-layer numerics: chunked implementations vs oracles, cache
consistency, MoE routing invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import flash, moe, ssm, xlstm
from repro.models.model import Model


# ------------------------------------------------------------- flash

@pytest.mark.parametrize("S,T,Hq,Hkv,qc,kc", [
    (37, 37, 8, 2, 16, 8),
    (64, 64, 4, 4, 64, 64),
    (17, 17, 6, 3, 5, 7),
])
def test_flash_matches_reference(S, T, Hq, Hkv, qc, kc):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S * T), 3)
    q = jax.random.normal(k1, (2, S, Hq, 16))
    k = jax.random.normal(k2, (2, T, Hkv, 16))
    v = jax.random.normal(k3, (2, T, Hkv, 16))
    out = flash.flash_attention(q, k, v, causal=True, q_chunk=qc,
                                kv_chunk=kc)
    ref = flash.attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_sliding_window():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (1, 50, 4, 16))
    k = jax.random.normal(k2, (1, 50, 2, 16))
    v = jax.random.normal(k3, (1, 50, 2, 16))
    out = flash.flash_attention(q, k, v, causal=True, window=11,
                                q_chunk=16, kv_chunk=8)
    ref = flash.attention_reference(q, k, v, causal=True, window=11)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------------- SSD

def test_ssd_chunked_equals_recurrence():
    cfg = get_config("zamba2-7b", reduced=True)
    p = ssm.ssm_init(jax.random.PRNGKey(3), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (2, 67, cfg.d_model))
    y1 = ssm.ssm_forward(p, x, cfg)
    y2 = ssm.ssm_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


# ------------------------------------------------------------- xLSTM

def test_mlstm_chunked_equals_recurrence():
    cfg = get_config("xlstm-125m", reduced=True)
    pm = xlstm.mlstm_init(jax.random.PRNGKey(5), cfg)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (2, 50, cfg.d_model))
    y1 = xlstm.mlstm_forward(pm, x, cfg, chunk=16)
    cache = xlstm.mlstm_cache_init(cfg, 2)
    outs = []
    for t in range(50):
        o, cache = xlstm.mlstm_decode(pm, x[:, t:t + 1], cache, cfg)
        outs.append(o)
    y2 = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


@pytest.mark.parametrize("shift", [0.0, -120.0])
def test_mlstm_gradient_finite_with_negative_input_gate(shift):
    """An input-gate pre-activation below -88.7 made exp(-m) overflow in
    the normaliser: the loss stayed finite but every gradient upstream was
    NaN (first seen in full-width xlstm-125m QN training). The output is
    unchanged and the gradient finite."""
    cfg = get_config("xlstm-125m", reduced=True)
    pm = xlstm.mlstm_init(jax.random.PRNGKey(5), cfg)
    H = cfg.n_heads
    pm["b_if"] = pm["b_if"].at[:H].add(shift)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (2, 50, cfg.d_model))

    def f(p):
        return jnp.sum(xlstm.mlstm_forward(p, x, cfg, chunk=16) ** 2)
    val, grads = jax.value_and_grad(f)(pm)
    assert np.isfinite(float(val))
    for g in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
    if shift:
        # the input gate shuts: output (and loss) near zero, not NaN
        assert float(val) < 1e-20
        cache = xlstm.mlstm_cache_init(cfg, 2)
        o, _ = xlstm.mlstm_decode(pm, x[:, :1], cache, cfg)
        g = jax.grad(lambda p: jnp.sum(xlstm.mlstm_decode(
            p, x[:, :1], cache, cfg)[0] ** 2))(pm)
        assert all(np.isfinite(np.asarray(v)).all()
                   for v in jax.tree_util.tree_leaves(g))


# ------------------------------------------- prefill/decode consistency

@pytest.mark.parametrize("arch", ["glm4-9b", "zamba2-7b", "xlstm-125m",
                                  "musicgen-medium"])
def test_prefill_equals_decode(arch):
    cfg = get_config(arch, reduced=True)
    m = Model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 2, 24
    if cfg.family == "audio":
        toks = jax.random.randint(jax.random.PRNGKey(1),
                                  (B, S, cfg.n_codebooks), 0, cfg.vocab)
    else:
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab)
    logits_full, _ = m.forward(p, {"tokens": toks})
    cache = m.init_cache(B, S)
    step = jax.jit(m.decode_step)
    logs = []
    for t in range(S):
        tok = toks[:, t:t + 1]
        lg, cache = step(p, cache, {"tokens": tok})
        logs.append(lg)
    logits_dec = jnp.concatenate(logs, 1)
    np.testing.assert_allclose(np.asarray(logits_full, np.float32),
                               np.asarray(logits_dec, np.float32),
                               atol=5e-4, rtol=1e-3)


def test_sliding_window_decode_matches_windowed_prefill():
    cfg = get_config("glm4-9b", reduced=True).with_sliding_window(8)
    m = Model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 1, 20
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    logits_full, _ = m.forward(p, {"tokens": toks})
    cache = m.init_cache(B, S)     # ring buffer of size 8
    step = jax.jit(m.decode_step)
    logs = []
    for t in range(S):
        lg, cache = step(p, cache, {"tokens": toks[:, t:t + 1]})
        logs.append(lg)
    logits_dec = jnp.concatenate(logs, 1)
    np.testing.assert_allclose(np.asarray(logits_full),
                               np.asarray(logits_dec), atol=5e-4, rtol=1e-3)


# --------------------------------------------------------------- MoE

def test_moe_router_load_and_gates():
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y, stats = moe.moe_ffn(p, x, cfg)
    assert y.shape == x.shape
    assert jnp.isfinite(stats["aux_loss"])
    np.testing.assert_allclose(float(stats["load_frac"].sum()), 1.0,
                               atol=1e-5)
    assert float(stats["dropped_frac"]) < 0.5


def test_moe_capacity_overflow_drops_not_corrupts():
    """With capacity_factor tiny, output stays finite and bounded."""
    import dataclasses
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    y, stats = moe.moe_ffn(p, x, cfg)
    assert jnp.isfinite(y).all()
    assert float(stats["dropped_frac"]) > 0.0


def test_vlm_patch_positions_not_scored():
    cfg = get_config("llava-next-mistral-7b", reduced=True)
    m = Model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                     cfg.vocab),
        "patch_embeds": jax.random.normal(jax.random.PRNGKey(3),
                                          (B, cfg.n_patches, 1024)),
    }
    loss, _ = m.loss(p, batch)
    assert jnp.isfinite(loss)
    logits, _ = m.forward(p, batch)
    assert logits.shape[1] == S + cfg.n_patches
