"""Algorithm 1: robust distributed quasi-Newton estimation with DP (§4).

Single-host reference implementation: machines are a leading axis, local
computations are vmapped, "transmissions" are explicit arrays so Byzantine
corruption and DP noise are applied exactly where the paper applies them
(on the wire). Every machine-local computation is routed through a
pluggable ``machine_map`` (default: jax.vmap); the shard_map SPMD version
(dist/sharded_protocol.py) swaps in a mesh-sharded map and reuses all the
central math below verbatim, so the two agree up to collective reduction
order (tested in tests/test_dist.py).

Every center-side reduction — the per-round aggregation AND the
untrusted-center median/variance plug-ins — routes through the
``repro.agg`` registry (jnp reference off-TPU, the batched Pallas
order-statistics kernel on TPU), so the protocol inherits any newly
registered aggregator via ``cfg.aggregator``. Symmetrically, every wire
corruption routes through the ``repro.attacks`` registry: the ``attack``
argument names a registered threat model, corruption is applied where the
full machine axis is visible (omniscient attacks read honest-row
statistics), and round-aware attacks receive the transmission index.

Round structure (five p-vector transmissions):
  R1  theta_hat_j + b1          -> DCQ -> theta_cq            (4.2)/(4.4)
  R2  grad_j(theta_cq) + b2     -> DCQ -> g_cq                (4.6)
  R3  Hinv_j g_cq + b3          -> DCQ -> H1; theta_os        (4.7)/(4.8)
  R4  grad-diff + b4            -> DCQ -> gdiff_cq, g_os      (4.12)
  R5  V^T Hinv_j V g_os + b5    -> DCQ -> H2; theta_qn        (4.15)

In ``center_trust="untrusted"`` mode (§4.3) the node machines additionally
transmit DP gradient variances ("R2b var"), making SIX DP transmissions;
the per-transmission budget is eps/6 so basic composition still totals the
configured (eps, delta).

Compile-once engine: ``protocol_rounds`` is a *pure* function of arrays and
static config — no ``float()`` on traced values, no Python-side accountant
mutation — so it jits once per (shape, config) and vmaps over Monte-Carlo
replicate keys. ``DPQNProtocol`` is the thin stateful shell: ``run`` calls
the cached compiled core and reconstructs ``PrivacyAccountant``/``noise_sd``
from the returned spend ledger *outside* the traced region;
``run_monte_carlo`` batches the core over replicate keys with a single
jit(vmap(...)) trace.

Indexing note: the paper takes the median over machines [m]_0 but sums the
CQ correction over node machines [m] only; we aggregate uniformly over all
m+1 transmitted values (an O(1/m) difference, recorded in DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.agg import median_deviation_variance
from repro.configs.base import ProtocolConfig, TreeProtocolConfig
from repro.core import dp, local
from repro.core import transport
from repro.core.bfgs import (LBFGSMemory, VOp, lbfgs_gamma,
                             lbfgs_two_loop_tree, make_v)
from repro.core.losses import MEstimationProblem
from repro.core.transport import (tree_add, tree_axpy, tree_sub,
                                  wire_aggregate, wire_corrupt, wire_noise)
from repro.spans import (ALG1_CENTER, ALG1_LOCAL, ALG1_WIRE,
                         PROTOCOL_DISPATCH, PROTOCOL_TRACE, scope, span)


def vmap_machines(fn, *machine_args, bcast=()):
    """Default machine map: vmap ``fn`` over the leading machine axis of
    ``machine_args``; ``bcast`` entries are passed whole to every machine.
    dist/sharded_protocol.py provides the mesh-sharded drop-in."""
    return jax.vmap(lambda *ma: fn(*ma, *bcast))(*machine_args)


def at_center(fn, *args):
    """Default center placement: the center's aggregation ``fn(*args)``
    runs where it is traced. dist/sharded_protocol.py provides the form
    for a machine mesh (``center_map``)."""
    return fn(*args)


def center_aggregate(center, values, method: str, scale=None, **kw):
    """``wire_aggregate`` of one transmission, placed by ``center``."""
    return center(lambda v, s: wire_aggregate(v, method, scale=s, **kw),
                  values, scale)


def monte_carlo_mrse(thetas: jnp.ndarray, target: jnp.ndarray) -> float:
    """Mean root-square error over the replicate axis of a
    ``run_monte_carlo`` output field: thetas (reps, p), target (p,)."""
    return float(jnp.mean(jnp.linalg.norm(thetas - target, axis=-1)))


# ------------------------------------------------------------ budget layout

#: transmission name -> reported-noise key in ``ProtocolResult.noise_sd``
_SD_KEY = {"R1 theta": "s1", "R2 grad": "s2", "R2b var": "s6",
           "R3 newton-dir": "s3", "R4 grad-diff": "s4", "R5 bfgs-dir": "s5"}


def transmission_names(cfg: ProtocolConfig) -> Tuple[str, ...]:
    """The DP transmissions Algorithm 1 performs under ``cfg``, in order.

    Trusted center: the five p-vector rounds. Untrusted center (§4.3): the
    node machines additionally transmit DP gradient variances after R2.
    """
    names = ["R1 theta", "R2 grad", "R3 newton-dir", "R4 grad-diff",
             "R5 bfgs-dir"]
    if cfg.n_rounds != len(names):
        raise ValueError(
            f"Algorithm 1 performs exactly {len(names)} vector rounds; "
            f"cfg.n_rounds={cfg.n_rounds} would desynchronise the privacy "
            f"budget split from the actual transmissions")
    if cfg.center_trust == "untrusted":
        names.insert(2, "R2b var")
    return tuple(names)


def n_transmissions(cfg: ProtocolConfig) -> int:
    return len(transmission_names(cfg))


def round_budget(cfg: ProtocolConfig) -> Tuple[float, float]:
    """Per-transmission (eps, delta) so basic composition totals the budget.

    Derived from the ACTUAL number of DP transmissions in the configured
    mode — 6 in untrusted-center mode, not ``cfg.n_rounds = 5`` — so the
    accountant never over-spends (regression: tests/test_protocol_engine.py).
    """
    k = n_transmissions(cfg)
    return cfg.eps / k, cfg.delta / k


def accountant_round_budget(cfg: ProtocolConfig) -> Tuple[float, float]:
    """Per-transmission budget certified by ``cfg.accountant``.

    ``"basic"`` routes through :func:`round_budget` unchanged (the exact
    historical floats); other registry entries invert their composition
    host-side (repro.privacy) — e.g. "rdp" records the LARGER standalone
    per-round eps whose Renyi composition still totals (cfg.eps,
    cfg.delta).
    """
    if cfg.accountant == "basic":
        return round_budget(cfg)
    from repro.privacy import get_accountant
    return get_accountant(cfg.accountant).per_round(
        cfg.eps, cfg.delta, n_transmissions(cfg))


def calibrate_sigma_base(cfg: ProtocolConfig, p: int, n: int,
                         eps=None, delta=None, accountant=None) -> Tuple:
    """Per-transmission BASE noise sds (norm factors = 1), aligned with
    ``transmission_names``. The budget dependence of Algorithm 1's noise
    calibration lives entirely in these scalars, so the sweep executor can
    compute them host-side in float64 per scenario and batch them along a
    vmap axis (``protocol_rounds(sigma_base=...)``) — scenarios that differ
    only in (eps, delta) then share one compiled executable AND match the
    compile-once static path bit-for-bit.

    ``eps``/``delta`` override the totals in ``cfg``; Python floats keep
    exact ``math`` arithmetic, traced scalars route through the dual-mode
    dp.py calibration. ``accountant`` overrides ``cfg.accountant``: the
    basic Thm 4.5 sds are scaled by the accountant's noise-multiplier
    ratio vs basic (repro.privacy). "basic"/"subexp" sds are NEVER
    rescaled (ratio is the literal 1.0 and the multiply is skipped), so
    the default stays byte-identical to the committed golden; non-basic
    accountants bisect host-side and therefore need Python-float budgets.
    """
    eps_t = cfg.eps if eps is None else eps
    delta_t = cfg.delta if delta is None else delta
    acct = cfg.accountant if accountant is None else accountant
    k = n_transmissions(cfg)
    eps_r, delta_r = eps_t / k, delta_t / k
    nl = cfg.noiseless
    s1 = dp.s1_theta(p, n, cfg.gammas[0], eps_r, delta_r, 1.0, cfg.tail)
    s2 = dp.s2_grad(p, n, cfg.gammas[1], eps_r, delta_r, cfg.tail)
    s3 = 0.0 if nl else dp.s3_newton_dir(p, n, cfg.gammas[2], eps_r, delta_r,
                                         1.0, 1.0, cfg.tail)
    s4 = 0.0 if nl else dp.s4_grad_diff(p, n, cfg.gammas[3], eps_r, delta_r,
                                        1.0, cfg.tail)
    s5 = 0.0 if nl else dp.s5_bfgs_dir(p, n, cfg.gammas[4], eps_r, delta_r,
                                       1.0, 1.0, cfg.tail)
    out = [s1, s2, s3, s4, s5]
    if cfg.center_trust == "untrusted":
        out.insert(2, dp.s6_variance(p, n, 1.0, eps_r, delta_r))
    if acct != "basic":
        from repro.privacy import multiplier_ratio
        ratio = multiplier_ratio(acct, eps_t, delta_t, k)
        if ratio != 1.0:
            out = [s * ratio for s in out]
    return tuple(out)


def _failure_probs(cfg: ProtocolConfig, p: int, n: int) -> Tuple[float, ...]:
    """Per-transmission sensitivity-failure probabilities (Lemmas 4.3/4.4),
    aligned with ``transmission_names``. Static in shapes and config.

    High-probability accountants ("subexp") record the Lemma 4.4 failure
    probability for EVERY mean-mechanism transmission — each of R1..R5 is
    a release whose sensitivity bound only holds on the tail event; other
    accountants keep the historical R1/R2 records.
    """
    if cfg.accountant != "basic":
        from repro.privacy import get_accountant
        acct = get_accountant(cfg.accountant)
        if acct.failure_prob is not None:
            probs = [acct.failure_prob(p, n, g) for g in cfg.gammas]
            if cfg.center_trust == "untrusted":
                # Thm 4.6 variance release: sub-Gaussian bound at gamma=1.
                probs.insert(2, dp.mean_dp_failure_prob_subgauss(p, n,
                                                                 1.0, 1.0))
            return tuple(probs)
    f1 = dp.mean_dp_failure_prob_subexp(p, n, cfg.gammas[0], 1.0, 1.0)
    f2 = dp.mean_dp_failure_prob_subexp(p, n, cfg.gammas[1], 1.0, 1.0)
    probs = [f1, f2, 0.0, 0.0, 0.0]
    if cfg.center_trust == "untrusted":
        probs.insert(2, 0.0)
    return tuple(probs)


class ProtocolArrays(NamedTuple):
    """Everything ``protocol_rounds`` produces, as arrays only — a valid jit
    output and a valid vmap carrier. The stateful shell turns this back into
    ``ProtocolResult`` (accountant, noise_sd floats) outside the trace."""
    theta_cq: jnp.ndarray        # initial DCQ estimator (4.4)
    theta_os: jnp.ndarray        # one-stage estimator (4.8)
    theta_qn: jnp.ndarray        # final quasi-Newton estimator
    sigmas: jnp.ndarray          # (n_tx,) reported noise sd per transmission
    ledger_eps: jnp.ndarray      # (n_tx,) per-transmission eps spend
    ledger_delta: jnp.ndarray    # (n_tx,) per-transmission delta spend
    failure_probs: jnp.ndarray   # (n_tx,) sensitivity failure probabilities
    v_s: jnp.ndarray             # BFGS curvature pair: s = theta_os - theta_cq
    v_y: jnp.ndarray             # y = gdiff_cq
    v_rho: jnp.ndarray           # rho = 1 / (s . y)


@dataclasses.dataclass
class ProtocolResult:
    theta_cq: jnp.ndarray          # initial DCQ estimator (4.4)
    theta_os: jnp.ndarray          # one-stage estimator (4.8)
    theta_qn: jnp.ndarray          # final quasi-Newton estimator
    accountant: dp.PrivacyAccountant
    noise_sd: Dict[str, float]
    v_op: Optional[VOp] = None
    arrays: Optional[ProtocolArrays] = None


# ------------------------------------------------------------ the pure core

def protocol_rounds(key: jax.Array, X: jnp.ndarray, y: jnp.ndarray,
                    problem: MEstimationProblem, cfg: ProtocolConfig,
                    byz_mask: Optional[jnp.ndarray] = None,
                    attack: str = "scale", attack_factor=-3.0,
                    theta0: Optional[jnp.ndarray] = None,
                    theta_cq_override: Optional[jnp.ndarray] = None,
                    machine_map=vmap_machines,
                    eps=None, delta=None,
                    sigma_base=None, center=at_center) -> ProtocolArrays:
    """Paper Algorithm 1 as a pure function: arrays in, arrays out.

    jit-compatible with ``problem``/``cfg``/``attack``/``machine_map``
    static (they are baked into the trace; ``DPQNProtocol`` closes over
    them), and vmap-compatible over ``key`` for Monte-Carlo replicates.
    ``X``: (m+1, n, p), ``y``: (m+1, n); machine 0 is the central processor.

    ``eps``/``delta`` optionally override the TOTAL privacy budget in
    ``cfg`` and may be traced scalars; ``sigma_base`` optionally supplies
    the (n_tx,) per-transmission base noise sds from
    ``calibrate_sigma_base`` — the sweep executor computes them host-side
    in float64 per scenario and vmaps over them, so scenarios differing
    only in privacy budget share one compiled executable and reproduce the
    static path bit-for-bit. ``center`` places the center's aggregations
    (``at_center``, or ``dist.sharded_protocol.center_map`` on a mesh).
    """
    prob = problem
    m_plus_1, n, p = X.shape
    if eps is None and delta is None:
        eps_r, delta_r = accountant_round_budget(cfg)  # exact Python floats
    else:
        # Traced-budget path (the sweep's vmap axis): the ledger arrays
        # carry the basic eps/k share — the per-transmission budget a
        # non-basic accountant certifies is not traceable (bisection), so
        # the executor records it host-side in the artifact spend record.
        k_tx = n_transmissions(cfg)
        eps_r = (cfg.eps if eps is None else eps) / k_tx
        delta_r = (cfg.delta if delta is None else delta) / k_tx
    if sigma_base is None:
        sigma_base = calibrate_sigma_base(cfg, p, n, eps=eps, delta=delta)
    sb = dict(zip(transmission_names(cfg), sigma_base))
    sig = []                         # per-transmission reported noise sd
    if byz_mask is None:
        byz_mask = jnp.zeros((m_plus_1,), bool)
    else:
        # center (machine 0) is honest in trusted mode
        byz_mask = jnp.concatenate([jnp.zeros((1,), bool), byz_mask])
    with scope(ALG1_WIRE):
        keys = jax.random.split(key, 16)
    if theta0 is None:
        theta0 = jnp.zeros((p,), X.dtype)

    # The wire primitives are the shared pytree transport layer
    # (core/transport.py): on these flat single-leaf arrays they consume
    # each transmission key unsplit, so the refactor is byte-identical to
    # the historical inline expressions (tests/test_protocol_pytree.py).
    def corrupt(vals, kk, rnd):
        # rnd = 0-based transmission index (round-aware attacks ramp on
        # it); omniscient attacks see the full machine axis here, exactly
        # the coordinated-adversary view of the wire.
        with scope(ALG1_WIRE):
            return wire_corrupt(kk, vals, byz_mask, attack=attack,
                                factor=attack_factor, round_idx=rnd)

    def noise(kk, x, s):
        with scope(ALG1_WIRE):
            return wire_noise(kk, x, s, noiseless=cfg.noiseless)

    # Device time is put down to three name scopes (repro.spans): the
    # machines' local work (ALG1_LOCAL), the wire's noise and corruption
    # (ALG1_WIRE) and the center (ALG1_CENTER).
    with scope(ALG1_CENTER):
        Xc, yc = X[0], y[0]  # center's own shard

    # ---- Round 1: local M-estimators -> theta_cq ----------------------
    with scope(ALG1_LOCAL):
        theta_local = machine_map(
            lambda Xi, yi, t0: local.newton_solve(prob, t0, Xi, yi,
                                                  steps=cfg.newton_steps),
            X, y, bcast=(theta0,))
        # lambda_s (Assumption 7.3): fixed constant, or calibrated by EACH
        # machine from its local Hessian spectrum (local data only => no
        # extra transmission, no extra privacy cost). The center uses its
        # own lambda_0 when reconstructing the noise variance.
        if cfg.lambda_s is None:
            lam_j = machine_map(lambda Xi, yi, ti: jnp.clip(
                jnp.linalg.eigvalsh(prob.hessian(ti, Xi, yi))[0], 1e-3,
                None), X, y, theta_local)
        else:
            lam_j = jnp.full((m_plus_1,), cfg.lambda_s, X.dtype)
    s1_base = sb["R1 theta"]
    with scope(ALG1_WIRE):
        s1_j = s1_base / lam_j                     # per-machine sd
    with scope(ALG1_CENTER):
        s1 = center_aggregate(center, s1_j, "median")  # reported value
    theta_dp = noise(keys[0], theta_local, s1_j)   # per-machine (m+1,) sd
    theta_dp = corrupt(theta_dp, keys[1], 0)
    sig.append(s1)

    with scope(ALG1_CENTER):
        theta_med = center_aggregate(center, theta_dp, "median")
        if cfg.center_trust == "trusted":
            sig2 = local.sandwich_diag_variance(prob, theta_med, Xc, yc)
        else:
            # untrusted center: median aggregation, no variance needed here
            sig2 = jnp.ones((p,), X.dtype)
        s1_eff = 0.0 if cfg.noiseless else s1_j[0]     # center's estimate
        scale1 = jnp.sqrt((sig2 + n * s1_eff ** 2)) / jnp.sqrt(n)
        agg1 = "median" if cfg.center_trust == "untrusted" else cfg.aggregator
        theta_cq = center_aggregate(center, theta_dp, agg1, scale=scale1,
                                    K=cfg.K, trim_beta=cfg.trim_beta)
    if theta_cq_override is not None:
        # warm start / ablation hook: continue the protocol from a
        # caller-supplied initial estimate.
        theta_cq = theta_cq_override

    # ---- Round 2: gradients at theta_cq -> g_cq -----------------------
    with scope(ALG1_LOCAL):
        grads = machine_map(lambda Xi, yi, t: prob.grad(t, Xi, yi),
                            X, y, bcast=(theta_cq,))
    s2 = sb["R2 grad"]
    grads_dp = noise(keys[2], grads, s2)
    grads_dp = corrupt(grads_dp, keys[3], 1)
    sig.append(s2)

    s2_eff = 0.0 if cfg.noiseless else s2
    if cfg.center_trust == "trusted":
        with scope(ALG1_CENTER):
            gvar = local.grad_coordinate_variance(prob, theta_cq, Xc, yc)
    else:
        # §4.3: node machines transmit DP variances; center medians them.
        s6 = sb["R2b var"]
        # node machines only (m of m+1 rows): stays a plain vmap — the
        # slice does not divide a machine mesh evenly.
        with scope(ALG1_LOCAL):
            node_gvar = jax.vmap(lambda Xi, yi: prob.grad_variance(
                theta_cq, Xi, yi))(X[1:], y[1:])
        node_gvar = noise(keys[4], node_gvar, s6)
        with scope(ALG1_WIRE):
            node_gvar = wire_corrupt(keys[5], node_gvar, byz_mask[1:],
                                     attack=attack, factor=attack_factor,
                                     round_idx=1)
        with scope(ALG1_CENTER):
            gvar = center_aggregate(center, node_gvar, "median")
        sig.append(s6)
    with scope(ALG1_CENTER):
        scale2 = jnp.sqrt(jnp.maximum(gvar, 1e-12)
                          + n * s2_eff ** 2) / jnp.sqrt(n)
        g_cq = _agg_for(cfg, "grad", grads_dp, scale2, center)

    # ---- Round 3: Newton directions -> theta_os -----------------------
    def newton_dir(Xi, yi, t_cq, g):
        h = prob.hessian(t_cq, Xi, yi) + 1e-9 * jnp.eye(p, dtype=X.dtype)
        return jnp.linalg.solve(h, g)
    with scope(ALG1_LOCAL):
        dirs = machine_map(newton_dir, X, y, bcast=(theta_cq, g_cq))
    s3 = sb["R3 newton-dir"]
    with scope(ALG1_WIRE):
        dir_norm = jnp.linalg.norm(dirs, axis=1)      # per machine (Thm 4.5(3))
        s3_j = (s3 / lam_j) * dir_norm                 # per-machine sd
    dirs_dp = noise(keys[6], dirs, s3_j)           # per-machine (m+1,) sd
    dirs_dp = corrupt(dirs_dp, keys[7], 2)
    sig.append(s3)

    with scope(ALG1_CENTER):
        if cfg.center_trust == "trusted":
            hvar = local.newton_dir_variance(prob, theta_cq, Xc, yc, g_cq)
        else:
            hvar = median_deviation_variance(dirs_dp, n)
        s3_0 = (s3 / lam_j[0]) * jnp.linalg.norm(dirs[0])
        scale3 = jnp.sqrt(jnp.maximum(hvar, 1e-12)
                          + n * s3_0 ** 2) / jnp.sqrt(n)
        H1 = _agg_for(cfg, "dir", dirs_dp, scale3, center)
        theta_os = theta_cq - H1

    # ---- Round 4: gradient differences -> gdiff_cq, g_os --------------
    with scope(ALG1_LOCAL):
        gdiff = machine_map(lambda Xi, yi, t_os, t_cq: prob.grad(t_os, Xi, yi)
                            - prob.grad(t_cq, Xi, yi),
                            X, y, bcast=(theta_os, theta_cq))
    with scope(ALG1_CENTER):
        step = theta_os - theta_cq
    s4 = sb["R4 grad-diff"]
    with scope(ALG1_WIRE):
        s4_eff = s4 * jnp.linalg.norm(step)
    gdiff_dp = noise(keys[8], gdiff, s4_eff)
    gdiff_dp = corrupt(gdiff_dp, keys[9], 3)
    sig.append(s4)

    with scope(ALG1_CENTER):
        if cfg.center_trust == "trusted":
            gd = prob.per_sample_grads(theta_os, Xc, yc) \
                - prob.per_sample_grads(theta_cq, Xc, yc)
            gdvar = jnp.var(gd, axis=0)
            gosvar = local.grad_coordinate_variance(prob, theta_os, Xc, yc)
        else:
            gdvar = median_deviation_variance(gdiff_dp, n)
            gosvar = gvar
        scale4 = jnp.sqrt(jnp.maximum(gdvar, 1e-12)
                          + n * s4_eff ** 2) / jnp.sqrt(n)
        gdiff_cq = _agg_for(cfg, "gdiff", gdiff_dp, scale4, center)
        scale4b = jnp.sqrt(jnp.maximum(gosvar, 1e-12) + n * s2_eff ** 2
                           + n * s4_eff ** 2) / jnp.sqrt(n)
        g_os = _agg_for(cfg, "g_os", grads_dp + gdiff_dp, scale4b,
                        center)

    # ---- Round 5: BFGS directions -> theta_qn --------------------------
    with scope(ALG1_CENTER):
        v = make_v(s=step, y=gdiff_cq)

    def bfgs_dir(Xi, yi, t_cq, vs, vy, vrho, g):
        vop = VOp(s=vs, y=vy, rho=vrho)
        h = prob.hessian(t_cq, Xi, yi) + 1e-9 * jnp.eye(p, dtype=X.dtype)
        hinv_vg = jnp.linalg.solve(h, vop(g, transpose=False))
        return vop(hinv_vg, transpose=True)            # (4.15) machine part
    with scope(ALG1_LOCAL):
        h3 = machine_map(bfgs_dir, X, y,
                         bcast=(theta_cq, v.s, v.y, v.rho, g_os))
    s5 = sb["R5 bfgs-dir"]
    with scope(ALG1_WIRE):
        s5_j = s5 * jnp.linalg.norm(h3, axis=1)
    h3_dp = noise(keys[10], h3, s5_j)              # per-machine (m+1,) sd
    h3_dp = corrupt(h3_dp, keys[11], 4)
    sig.append(s5)

    with scope(ALG1_CENTER):
        if cfg.center_trust == "trusted":
            h3var = local.bfgs_dir_variance(prob, theta_cq, Xc, yc, v, g_os)
        else:
            h3var = median_deviation_variance(h3_dp, n)
        s5_0 = s5 * jnp.linalg.norm(h3[0])
        scale5 = jnp.sqrt(jnp.maximum(h3var, 1e-12)
                          + n * s5_0 ** 2) / jnp.sqrt(n)
        h3_agg = _agg_for(cfg, "h3", h3_dp, scale5, center)
        # center-side rank-1 term: rho (s s^T) g_os  (below eq. 4.15)
        H2 = h3_agg + v.rho * step * jnp.dot(step, g_os)
        theta_qn = theta_os - H2

    k = n_transmissions(cfg)
    assert len(sig) == k, "spend ledger out of sync with transmission_names"
    return ProtocolArrays(
        theta_cq=theta_cq, theta_os=theta_os, theta_qn=theta_qn,
        sigmas=jnp.stack([jnp.asarray(s, jnp.float32) for s in sig]),
        ledger_eps=jnp.full((k,), eps_r, jnp.float32),
        ledger_delta=jnp.full((k,), delta_r, jnp.float32),
        failure_probs=jnp.asarray(_failure_probs(cfg, p, n), jnp.float32),
        v_s=v.s, v_y=v.y, v_rho=v.rho)


def _agg_for(cfg: ProtocolConfig, name: str, values, scale, center):
    """Untrusted-center mode uses the median everywhere except the gradient
    round (paper §4.3 keeps DCQ for 'crucial statistics such as gradients').

    Routed through the pytree transport layer: flat arrays hit the
    registry verbatim (byte parity), pytrees dispatch per leaf.
    """
    if cfg.center_trust == "untrusted" and name not in ("grad",):
        return center_aggregate(center, values, "median")
    return center_aggregate(center, values, cfg.aggregator, scale=scale,
                            K=cfg.K, trim_beta=cfg.trim_beta)


# ---------------------------------------------- pytree (model-scale) engine

class ProtocolTreeArrays(NamedTuple):
    """Output of one pytree protocol step — arrays/pytrees only, a valid
    jit output and scan carrier. ``mem`` is the updated per-machine L-BFGS
    history the trainer threads into the next step."""
    theta_cq: object         # robustly aggregated params after R1
    theta_os: object         # one-stage params after R3
    theta_qn: object         # final quasi-Newton params after R5
    v_s: object              # curvature pair: s = theta_os - theta_cq
    v_y: object              # y = aggregated grad-diff (R4)
    mem: LBFGSMemory         # per-machine (s, y) history, machine axis first
    losses: jnp.ndarray      # (m,) machine-local losses at the incoming theta
    grad_norm: jnp.ndarray   # ||g_cq|| over the whole tree


def protocol_tree_rounds(key: jax.Array, theta, batches, grad_fn,
                         cfg: TreeProtocolConfig,
                         mem: Optional[LBFGSMemory] = None,
                         byz_mask: Optional[jnp.ndarray] = None,
                         attack: str = "none", attack_factor=-3.0,
                         sigmas=None, n: Optional[int] = None,
                         machine_map=vmap_machines,
                         center=at_center) -> ProtocolTreeArrays:
    """Algorithm 1's five transmissions over an arbitrary parameter pytree
    — one robust DP quasi-Newton training step for the model zoo.

    The SAME wire primitives as the flat path (core/transport.py), so
    every transmission is noised per leaf (per-leaf DP calibration),
    corrupted through the ``repro.attacks`` registry, and aggregated per
    leaf through ``repro.agg``. The round mapping from the convex head:

      R1  machine-local SGD steps -> theta_j     -> agg -> theta_cq  (4.4)
      R2  grad_j(theta_cq)                       -> agg -> g_cq      (4.6)
      R3  per-machine L-BFGS dir on g_cq         -> agg -> H1;
          theta_os = theta_cq - lr * H1                              (4.8)
      R4  grad_j(theta_os) - grad_j(theta_cq)    -> agg -> y;
          s = theta_os - theta_cq                                    (4.12)
      R5  push (s, y_j^local) into machine memory; L-BFGS dir on
          g_os = g_cq + y                        -> agg -> H2;
          theta_qn = theta_os - lr * H2                              (4.15)

    Machine-local curvature: each machine pushes its OWN raw grad-diff
    (local data never leaves the machine un-noised) — the L-BFGS analog of
    the paper's machine-side H_j^{-1}; the dense p x p update of the
    convex head is replaced by the two-loop recursion over ``cfg.hist``
    (s, y) pairs.

    Pure and compile-once like ``protocol_rounds``: jit with ``grad_fn``,
    ``cfg``, ``attack``, ``machine_map``, ``center`` static; vmap over
    ``key`` for replicates. ``batches``: pytree with leading machine axis m;
    ``grad_fn(theta, batch) -> (loss, grad_tree)``. ``sigmas`` overrides
    the per-leaf calibration ({transmission: sigma pytree},
    dp.calibrate_tree_sigmas); otherwise it is computed here from
    ``cfg.eps`` and ``n`` (samples per machine). ``cfg.eps <= 0`` runs
    noiseless.
    """
    m = jax.tree_util.tree_leaves(batches)[0].shape[0]
    noiseless = cfg.eps <= 0.0
    if sigmas is None and not noiseless:
        if n is None:
            raise ValueError("per-leaf DP calibration needs n (samples per "
                             "machine) when sigmas are not supplied")
        sigmas = dp.calibrate_tree_sigmas(theta, n, cfg.eps, cfg.delta,
                                          cfg.gammas, cfg.tail,
                                          accountant=cfg.accountant)
    if sigmas is None:
        sigmas = {name: 0.0 for name in dp.TREE_TRANSMISSIONS}
    if byz_mask is None:
        byz_mask = jnp.zeros((m,), bool)
    if mem is None:
        mem = LBFGSMemory.init_like(cfg.hist, theta, machines=m)
    # Same 16-way key layout as the flat path (indices 4/5 reserved for
    # the untrusted-center variance round).
    keys = jax.random.split(key, 16)

    def tx(name, rnd, k_noise, k_corrupt, values):
        vals = wire_noise(k_noise, values, sigmas[name], noiseless=noiseless)
        vals = wire_corrupt(k_corrupt, vals, byz_mask, attack=attack,
                            factor=attack_factor, round_idx=rnd)
        return center_aggregate(center, vals, cfg.aggregator, K=cfg.K,
                                trim_beta=cfg.trim_beta)

    # ---- R1: machine-local steps -> theta_cq --------------------------
    def local_fit(batch):
        def step(t, _):
            loss, g = grad_fn(t, batch)
            return tree_axpy(-cfg.local_lr, g, t), loss
        t, losses = jax.lax.scan(step, theta, None, length=cfg.local_steps)
        return t, losses[0]
    theta_j, loss_j = machine_map(local_fit, batches)
    theta_cq = tx("R1 theta", 0, keys[0], keys[1], theta_j)

    # ---- R2: gradients at theta_cq -> g_cq ----------------------------
    g_j = machine_map(lambda b, t: grad_fn(t, b)[1], batches,
                      bcast=(theta_cq,))
    g_cq = tx("R2 grad", 1, keys[2], keys[3], g_j)

    # ---- R3: per-machine L-BFGS directions -> theta_os ----------------
    dir_j = machine_map(
        lambda mm, g: lbfgs_two_loop_tree(mm, g, gamma=lbfgs_gamma(mm)),
        mem, bcast=(g_cq,))
    H1 = tx("R3 newton-dir", 2, keys[6], keys[7], dir_j)
    theta_os = tree_axpy(-cfg.lr, H1, theta_cq)
    s_pair = tree_sub(theta_os, theta_cq)

    # ---- R4: gradient differences -> y --------------------------------
    y_j = machine_map(
        lambda b, t_os, t_cq: tree_sub(grad_fn(t_os, b)[1],
                                       grad_fn(t_cq, b)[1]),
        batches, bcast=(theta_os, theta_cq))
    y_cq = tx("R4 grad-diff", 3, keys[8], keys[9], y_j)

    # ---- R5: curvature push + L-BFGS directions -> theta_qn -----------
    def safe_push(mm, yj, s):
        # skip non-curvature pairs (s.y <= 0 would break the two-loop
        # positive-definiteness); each machine keeps its LOCAL pair.
        ok = transport.tree_dot(s, yj) > 1e-10
        pushed = mm.push(s, yj)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(ok, a, b), pushed, mm)
    mem = machine_map(safe_push, mem, y_j, bcast=(s_pair,))
    g_os = tree_add(g_cq, y_cq)
    dir2_j = machine_map(
        lambda mm, g: lbfgs_two_loop_tree(mm, g, gamma=lbfgs_gamma(mm)),
        mem, bcast=(g_os,))
    H2 = tx("R5 bfgs-dir", 4, keys[10], keys[11], dir2_j)
    theta_qn = tree_axpy(-cfg.lr, H2, theta_os)

    return ProtocolTreeArrays(
        theta_cq=theta_cq, theta_os=theta_os, theta_qn=theta_qn,
        v_s=s_pair, v_y=y_cq, mem=mem, losses=loss_j,
        grad_norm=jnp.sqrt(transport.tree_dot(g_cq, g_cq)).astype(
            jnp.float32))


# ------------------------------------------------------- the stateful shell

class DPQNProtocol:
    """Paper Algorithm 1. ``run`` consumes pre-sharded data:
    X: (m+1, n, p), y: (m+1, n); machine 0 is the central processor.

    The protocol core compiles ONCE per (attack, shape) signature and is
    reused across ``run`` calls; ``run_monte_carlo`` vmaps the same core
    over replicate keys. ``jit=False`` keeps the eager per-op path (used as
    the baseline in benchmarks/bench_protocol.py). ``trace_count`` counts
    how many times the core was (re)traced — tests assert a second call
    with identical shapes does not retrace. Each engine call opens the
    host span ``repro.protocol.dispatch`` (trace, lowering, cache fetch,
    launch), and each trace ``repro.protocol.trace`` inside it
    (repro.spans).
    """

    def __init__(self, problem: MEstimationProblem, cfg: ProtocolConfig,
                 machine_map=None, jit: bool = True, center=None):
        self.problem = problem
        self.cfg = cfg
        # machine_map(fn, *machine_args, bcast=()) runs fn once per machine
        # and center(fn, *args) runs the center's aggregations; the SPMD
        # protocol passes shard_map-based implementations of both.
        self._mmap = machine_map or vmap_machines
        self._center = center or at_center
        self._jit = jit
        self.trace_count = 0
        self._engines = {}   # attack -> (single, batched)

    def _engine(self, attack: str):
        """(single, batched-over-keys) callables for one attack mode; built
        lazily and cached so jit compiles once per protocol instance."""
        if attack not in self._engines:
            def rounds(key, X, y, byz_mask, theta0, theta_cq_override,
                       attack_factor):
                self.trace_count += 1
                with span(PROTOCOL_TRACE):
                    return protocol_rounds(
                        key, X, y, self.problem, self.cfg,
                        byz_mask=byz_mask, attack=attack,
                        attack_factor=attack_factor, theta0=theta0,
                        theta_cq_override=theta_cq_override,
                        machine_map=self._mmap, center=self._center)
            batched = jax.vmap(rounds, in_axes=(0,) + (None,) * 6)
            if self._jit:
                rounds, batched = jax.jit(rounds), jax.jit(batched)
            self._engines[attack] = (rounds, batched)
        return self._engines[attack]

    def _finalize(self, arrays: ProtocolArrays) -> ProtocolResult:
        """Rebuild the Python-side accountant from the spend ledger, OUTSIDE
        any traced region. eps/delta come from the static budget split
        (exact Python floats); sigmas/failure probs from the ledger arrays."""
        names = transmission_names(self.cfg)
        eps_r, delta_r = accountant_round_budget(self.cfg)
        acct = dp.PrivacyAccountant()
        noise_sd: Dict[str, float] = {}
        for i, name in enumerate(names):
            sigma = float(arrays.sigmas[i])
            acct.spend(name, eps_r, delta_r, sigma,
                       float(arrays.failure_probs[i]))
            noise_sd[_SD_KEY[name]] = sigma
        v = VOp(s=arrays.v_s, y=arrays.v_y, rho=arrays.v_rho)
        return ProtocolResult(
            theta_cq=arrays.theta_cq, theta_os=arrays.theta_os,
            theta_qn=arrays.theta_qn, accountant=acct, noise_sd=noise_sd,
            v_op=v, arrays=arrays)

    # -- single replicate ---------------------------------------------------
    def run(self, key: jax.Array, X: jnp.ndarray, y: jnp.ndarray,
            byz_mask: Optional[jnp.ndarray] = None,
            attack: str = "scale", attack_factor: float = -3.0,
            theta0: Optional[jnp.ndarray] = None,
            theta_cq_override: Optional[jnp.ndarray] = None) -> ProtocolResult:
        single, _ = self._engine(attack)
        with span(PROTOCOL_DISPATCH):
            arrays = single(key, X, y, byz_mask, theta0, theta_cq_override,
                            attack_factor)
        return self._finalize(arrays)

    # -- batched Monte-Carlo driver ----------------------------------------
    def run_monte_carlo(self, keys: jax.Array, X: jnp.ndarray,
                        y: jnp.ndarray,
                        byz_mask: Optional[jnp.ndarray] = None,
                        attack: str = "scale", attack_factor: float = -3.0,
                        theta0: Optional[jnp.ndarray] = None,
                        theta_cq_override: Optional[jnp.ndarray] = None
                        ) -> ProtocolArrays:
        """Run ``len(keys)`` independent replicates of Algorithm 1 in one
        compiled vmap: jit once, batch over the replicate axis. Returns a
        ``ProtocolArrays`` whose every field has a leading replicate axis
        (e.g. ``theta_qn``: (reps, p)). Data/masks are shared across
        replicates; only the PRNG key varies."""
        _, batched = self._engine(attack)
        with span(PROTOCOL_DISPATCH):
            return batched(keys, X, y, byz_mask, theta0, theta_cq_override,
                           attack_factor)
