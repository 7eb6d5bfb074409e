"""Entry ``mc``: Monte-Carlo fits of Algorithm 1 through
``repro.api.run_monte_carlo``, called in a closed loop and unwrapped, as
a statistician's script calls it.

Set-up makes the data from ``--seed`` on the device and makes the
traffic's calls twice. The window then makes call after call, each with
fresh replicate keys, until ``--seconds`` have passed; it ends with the
last call that started inside it. ``fits_per_s`` is the replicates of
all calls over the window's time.

``correct``: once the window has closed, ``compare_calls`` of its calls,
drawn from the seed, are recomputed by the plain reference
(``bench/reference/alg1_logistic.py``) from the same keys and data, and
every replicate's theta_qn is compared (:func:`readings`).
"""
from __future__ import annotations

import time

import numpy as np


def readings(theta: np.ndarray, theta_ref: np.ndarray,
             target: np.ndarray, moved_at: float) -> dict:
    """Program against reference, replicate by replicate.

    ``rel``: per replicate, max |theta - theta_ref| / max |theta_ref|.
    ``rel_p50``: its median; ``moved``: the share of replicates whose
    ``rel`` exceeds ``moved_at``. Compared are these two; ``mrse_gap``,
    the relative gap between the two mean root-square errors against
    theta*, is logged only: one replicate moved by an indicator flip
    carries it (PERF.md).
    """
    theta = np.asarray(theta, np.float64)
    theta_ref = np.asarray(theta_ref, np.float64)
    rel = np.abs(theta - theta_ref).max(-1) \
        / np.maximum(np.abs(theta_ref).max(-1), 1e-12)
    rel = np.where(np.isfinite(rel), rel, np.inf)
    mrse = np.linalg.norm(theta - target, axis=-1).mean()
    mrse_ref = np.linalg.norm(theta_ref - target, axis=-1).mean()
    return {"rel_p50": float(np.median(rel)),
            "rel_p90": float(np.quantile(rel, 0.9)),
            "rel_max": float(rel.max()),
            "moved": float((rel > moved_at).mean()),
            "mrse_gap": float(abs(mrse - mrse_ref) / mrse_ref)}


def sample_calls(seed: int, n_calls: int, k: int) -> list:
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    return sorted(rng.choice(n_calls, size=min(k, n_calls),
                             replace=False).tolist())


def kernel_bytes_per_call(cf: dict, reps: int) -> int:
    """Order-statistics kernel bytes of one call from its shapes: per
    replicate, Algorithm 1's center aggregates over m+1 machine rows the
    per-machine noise scale (a median), theta (a median, then DCQ) and
    the four later transmissions plus g_os (DCQ with a scale)."""
    from bench.counts import ostat_bytes
    rows, p = cf["machines"] + 1, cf["p"]
    return (ostat_bytes((reps, rows, 1), 4)
            + ostat_bytes((reps, rows, p), 4)
            + 6 * ostat_bytes((reps, rows, p), 4, scale=True))


def program(r, X, y, fault=None):
    """The timed call, ``keys -> theta_qn (reps, p)``; ``fault`` plants
    one of the faults the benchmark's tests must catch. The calls run at
    the configuration's matrix-product precision."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.configs.base import ProtocolConfig

    cf, tr = r.config, r.traffic
    pcfg = ProtocolConfig(
        eps=tr["eps"], delta=cf["delta"], K=cf["K"],
        gammas=tuple(cf["gammas"]), tail=cf["tail"],
        aggregator=cf["aggregator"], center_trust=cf["center_trust"],
        newton_steps=cf["newton_steps"])
    byz = jnp.arange(cf["machines"]) < tr["byzantine"]
    if fault == "half_batch":          # half of every shard left out
        X, y = X[:, : X.shape[1] // 2], y[:, : y.shape[1] // 2]

    def call(keys):
        with jax.default_matmul_precision(cf["matmul_precision"]):
            out = api.run_monte_carlo(X, y, cfg=pcfg, keys=keys,
                                      byz_mask=byz, attack=tr["attack"],
                                      attack_factor=tr["attack_factor"])
        if fault == "state_unchanged":  # the last round returns its input
            return out.theta_os
        if fault == "answer":           # answers altered where made
            return out.theta_qn.at[:, 0].multiply(-1.0)
        return out.theta_qn
    return call


def reference(r, precision="highest"):
    """``(keys, X, y) -> theta_qn`` of the plain reference, jitted, at
    ``precision`` (the control passes ``"high"``)."""
    import jax
    import jax.numpy as jnp
    from bench.reference.alg1_logistic import make_alg1

    cf, tr = r.config, r.traffic
    rep = make_alg1(cf["machines"], cf["n"], cf["p"], tr["eps"],
                    cf["delta"], tuple(cf["gammas"]), cf["tail"], cf["K"],
                    cf["newton_steps"], tr["attack_factor"], precision)
    byz = jnp.arange(cf["machines"]) < tr["byzantine"]
    batched = jax.jit(jax.vmap(lambda k, X, y: rep(k, X, y, byz),
                               in_axes=(0, None, None)))

    def call(keys, X, y):
        with jax.default_matmul_precision(precision):
            return batched(keys, X, y)
    return call


def run(r, fault=None):
    import jax
    from bench import data

    cf, tr = r.config, r.traffic
    reps = tr["reps_per_call"]
    with r.span("bench.setup.data"):
        X, y = data.logistic_shards(r.key("data"), cf["machines"],
                                    cf["n"], cf["p"], cf["rho"])
        jax.block_until_ready((X, y))
    root = r.key("replicates")
    split = jax.jit(lambda i: jax.random.split(jax.random.fold_in(root, i),
                                               reps))
    call = program(r, X, y, fault)
    with r.span("bench.setup.warmup"):
        for i in (2**31 - 1, 2**31 - 2):
            jax.block_until_ready(call(split(i)))

    thetas = []
    with r.window():
        t_end = time.perf_counter() + r.seconds
        while True:
            with r.span("bench.call"):
                th = jax.block_until_ready(call(split(len(thetas))))
            thetas.append(th)
            if time.perf_counter() >= t_end:
                break
    r.read_memory_peak()
    n_calls = len(thetas)
    counts = r.in_window()
    r.values.update(calls=n_calls, reps_per_call=reps,
                    ostat_bytes=n_calls * kernel_bytes_per_call(cf, reps),
                    traces_in_window=counts["traces"],
                    compiles_in_window=counts["compiles"]
                    - counts["cache_hits"])
    r.log(f"window: {n_calls} calls, {counts['traces']} traces, "
          f"{counts['lowerings']} lowerings, {counts['compiles']} "
          f"backend compiles of which {counts['cache_hits']} from the "
          f"persistent cache")

    thetas = np.stack([np.asarray(t) for t in thetas])
    failed = int((~np.isfinite(thetas).all(-1)).sum())
    picked = sample_calls(r.seed, n_calls, tr["compare_calls"])
    ref = reference(r)
    with r.span("bench.reference"):
        theta_ref = np.concatenate(
            [np.asarray(ref(split(i), X, y)) for i in picked])
    got = np.concatenate([thetas[i] for i in picked])
    lim = tr["limits"]          # PERF.md gives the readings behind them
    rd = readings(got, theta_ref, np.asarray(data.target_theta(cf["p"])),
                  lim["moved_at"])
    r.log(f"compared {len(got)} replicates of calls {picked}: {rd}")
    for name in ("rel_p50", "moved"):
        r.check(name, rd[name], lim[name])
    return {"correct": failed == 0, "attempted": n_calls * reps,
            "failed": failed,
            "metrics": {"fits_per_s": n_calls * reps / r.window_s}}


def calibrate(r, variant: str, cache: dict) -> dict:
    """The readings of one seed with no window: ``variant`` is
    ``"program"``, ``"control"`` (the reference at ``"high"``, three
    bfloat16 passes, the precision below the configuration's float32 at
    ``"highest"``, in the program's place) or a planted fault (see
    :func:`program`)."""
    import jax
    from bench import data

    cf, tr = r.config, r.traffic
    X, y = data.logistic_shards(r.key("data"), cf["machines"], cf["n"],
                                cf["p"], cf["rho"])
    root = r.key("replicates")
    keys = [jax.random.split(jax.random.fold_in(root, i),
                             tr["reps_per_call"])
            for i in range(tr["compare_calls"])]
    if variant == "control":
        low = reference(r, precision="high")
        got = [low(k, X, y) for k in keys]
    else:
        call = program(r, X, y, None if variant == "program" else variant)
        got = [call(k) for k in keys]
    ref = reference(r)
    theta_ref = np.concatenate([np.asarray(ref(k, X, y)) for k in keys])
    return readings(np.concatenate([np.asarray(g) for g in got]), theta_ref,
                    np.asarray(data.target_theta(cf["p"])),
                    tr["limits"]["moved_at"])
