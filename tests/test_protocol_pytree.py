"""Pytree-native protocol core: byte parity of the refactored flat path
against the pre-refactor golden fixture, single-leaf transport parity,
tree L-BFGS vs flat two-loop, per-leaf DP calibration (the grad_agg
global-sigma bugfix), compile-once on the zoo training path, and the
rewritten robust-training example."""
import importlib.util
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attacks import apply_attack
from repro.configs.base import TreeProtocolConfig
from repro.core import bfgs, dp
from repro.core.protocol import protocol_tree_rounds
from repro.core.transport import (tree_dot, tree_leaf_dims, tree_size,
                                  wire_aggregate, wire_corrupt, wire_noise)
from repro.dist.grad_agg import (GradAggConfig, add_dp_noise,
                                 calibrate_leaf_sigmas)
from repro.sweep import SweepExecutor, TrainScenario, build_preset

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "smoke_golden.json")


# ------------------------------------------------- transport layer parity

def test_wire_noise_single_leaf_byte_parity():
    """A single-leaf pytree must consume the transmission key UNSPLIT so
    flat arrays and {'theta': flat} draw identical noise."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 5))
    flat = wire_noise(key, x, 0.3)
    tree = wire_noise(key, {"theta": x}, 0.3)
    assert np.array_equal(np.asarray(flat), np.asarray(tree["theta"]))
    # multi-leaf trees split once per leaf -> leaves get DIFFERENT draws
    two = wire_noise(key, {"a": x, "b": x}, 0.3)
    assert not np.array_equal(np.asarray(two["a"]), np.asarray(two["b"]))


def test_wire_corrupt_single_leaf_byte_parity():
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 5))
    mask = jnp.arange(6) < 2
    flat = wire_corrupt(key, x, mask, attack="signflip", factor=-3.0,
                        round_idx=1)
    tree = wire_corrupt(key, {"theta": x}, mask, attack="signflip",
                        factor=-3.0, round_idx=1)
    assert np.array_equal(np.asarray(flat), np.asarray(tree["theta"]))
    # matches the registry applied directly
    direct = apply_attack(x, mask, attack="signflip", factor=-3.0,
                          key=key, round_idx=1)
    assert np.array_equal(np.asarray(flat), np.asarray(direct))


def test_wire_aggregate_single_leaf_byte_parity():
    x = jax.random.normal(jax.random.PRNGKey(2), (9, 5))
    for method in ("mean", "median", "dcq_mad", "trimmed"):
        flat = wire_aggregate(x, method=method)
        tree = wire_aggregate({"theta": x}, method=method)
        assert np.array_equal(np.asarray(flat),
                              np.asarray(tree["theta"])), method


def test_wire_aggregate_multi_leaf_shapes_and_dtype():
    vals = {"w": jax.random.normal(jax.random.PRNGKey(4), (7, 3, 4)),
            "b": jax.random.normal(jax.random.PRNGKey(5), (7, 2))}
    agg = wire_aggregate(vals, method="median")
    assert agg["w"].shape == (3, 4) and agg["b"].shape == (2,)
    assert agg["w"].dtype == vals["w"].dtype
    # per-leaf dispatch matches aggregating each leaf alone
    for name in vals:
        alone = wire_aggregate(vals[name], method="median")
        assert np.array_equal(np.asarray(agg[name]), np.asarray(alone))


def test_tree_size_and_dims():
    tree = {"w": jnp.zeros((4, 10, 3)), "b": jnp.zeros((4, 2))}
    dims = tree_leaf_dims(tree, machine_axis=True)
    assert dims == {"w": 30, "b": 2}
    assert tree_size({"w": jnp.zeros((10, 3)), "b": jnp.zeros((2,))}) == 32


# ----------------------------------------------------- L-BFGS tree parity

def test_lbfgs_two_loop_tree_matches_flat():
    p, hist = 6, 4
    key = jax.random.PRNGKey(11)
    mem_flat = bfgs.LBFGSMemory.init(hist, p)
    mem_tree = bfgs.LBFGSMemory.init_like(hist, {"theta": jnp.zeros(p)})
    for i in range(3):
        s = jax.random.normal(jax.random.fold_in(key, 2 * i), (p,))
        y = s + 0.1 * jax.random.normal(jax.random.fold_in(key, 2 * i + 1),
                                        (p,))
        mem_flat = mem_flat.push(s, y)
        mem_tree = mem_tree.push({"theta": s}, {"theta": y})
    g = jax.random.normal(jax.random.fold_in(key, 99), (p,))
    d_flat = bfgs.lbfgs_two_loop(mem_flat, g, gamma=0.7)
    d_tree = bfgs.lbfgs_two_loop_tree(mem_tree, {"theta": g}, gamma=0.7)
    assert np.array_equal(np.asarray(d_flat), np.asarray(d_tree["theta"]))
    # splitting the vector over two leaves preserves the direction (the
    # two-loop only consumes inner products, which sum over leaves)
    mem2 = bfgs.LBFGSMemory.init_like(
        hist, {"a": jnp.zeros(4), "b": jnp.zeros(2)})
    mem_flat2 = bfgs.LBFGSMemory.init(hist, p)
    for i in range(3):
        s = jax.random.normal(jax.random.fold_in(key, 2 * i), (p,))
        y = s + 0.1 * jax.random.normal(jax.random.fold_in(key, 2 * i + 1),
                                        (p,))
        mem2 = mem2.push({"a": s[:4], "b": s[4:]},
                         {"a": y[:4], "b": y[4:]})
        mem_flat2 = mem_flat2.push(s, y)
    d2 = bfgs.lbfgs_two_loop_tree(
        mem2, {"a": g[:4], "b": g[4:]}, gamma=0.7)
    np.testing.assert_allclose(
        np.concatenate([d2["a"], d2["b"]]),
        np.asarray(bfgs.lbfgs_two_loop(mem_flat2, g, gamma=0.7)),
        rtol=1e-5, atol=1e-6)


def test_lbfgs_two_loop_tree_keeps_mixed_leaf_dtypes():
    """Full-width models mix bf16 and f32 leaves; the f32 curvature
    scalars must not promote a bf16 leaf, or the scan carry changes type
    and the QN step does not trace."""
    key = jax.random.PRNGKey(5)
    dtypes = {"w": jnp.bfloat16, "b": jnp.float32}

    def draw(i):
        return {k: jax.random.normal(jax.random.fold_in(key, 2 * i + j),
                                     (4,)).astype(dt)
                for j, (k, dt) in enumerate(dtypes.items())}
    mem = bfgs.LBFGSMemory.init_like(
        3, {k: jnp.zeros(4, dt) for k, dt in dtypes.items()})
    mem32 = bfgs.LBFGSMemory.init_like(3, {k: jnp.zeros(4) for k in dtypes})
    for i in range(2):
        s = draw(i)
        y = jax.tree_util.tree_map(lambda v: (1.5 * v).astype(v.dtype), s)
        mem = mem.push(s, y)
        mem32 = mem32.push(*(jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32), t) for t in (s, y)))
    g = draw(7)

    def two_loop(mm, gg):       # the f32 Barzilai-Borwein gamma, as in R3
        return bfgs.lbfgs_two_loop_tree(mm, gg, gamma=bfgs.lbfgs_gamma(mm))
    d = jax.jit(two_loop)(mem, g)
    assert {k: v.dtype for k, v in d.items()} == dtypes
    d32 = two_loop(mem32, jax.tree_util.tree_map(
        lambda v: v.astype(jnp.float32), g))
    for k in dtypes:        # bf16 keeps 8 mantissa bits
        np.testing.assert_allclose(np.asarray(d[k], np.float32),
                                   np.asarray(d32[k]), rtol=3e-2,
                                   atol=3e-2)


# -------------------------------- per-leaf DP calibration (grad_agg fix)

def test_per_leaf_sigmas_scale_with_leaf_dimension():
    """REGRESSION (the historical grad_agg bug): two leaves with
    different dimensions must get different noise scales — the 16-d bias
    must NOT be noised like the 4096-d matrix."""
    g = {"w": jnp.zeros((4, 2000)), "b": jnp.zeros((4, 50))}
    cfg = GradAggConfig(dp_eps=1.0, dp_n=100)
    sig = calibrate_leaf_sigmas(g, cfg)
    assert sig["w"] != sig["b"]
    np.testing.assert_allclose(sig["w"] / sig["b"],
                               np.sqrt(2000 / 50), rtol=1e-6)
    # and the noise actually drawn matches each leaf's own sigma
    noised = add_dp_noise(g, sig, jax.random.PRNGKey(0))
    std_w = float(jnp.std(noised["w"]))
    std_b = float(jnp.std(noised["b"]))
    np.testing.assert_allclose(std_w, sig["w"], rtol=0.1)
    np.testing.assert_allclose(std_b, sig["b"], rtol=0.15)


def test_add_dp_noise_zero_sigma_noop():
    g = {"w": jnp.ones((3, 5))}
    assert add_dp_noise(g, 0.0, jax.random.PRNGKey(0)) is g


def test_calibrate_tree_sigmas_and_ledger():
    tree = {"w": jnp.zeros((10, 4)), "b": jnp.zeros((2,))}
    sigmas = dp.calibrate_tree_sigmas(tree, n=100, eps=5.0, delta=0.05)
    assert set(sigmas) == set(dp.TREE_TRANSMISSIONS)
    for name in dp.TREE_TRANSMISSIONS:
        assert sigmas[name]["w"] > sigmas[name]["b"]
    ledger = dp.tree_spend_ledger(tree, n=100, eps=5.0, delta=0.05)
    assert len(ledger) == len(dp.TREE_TRANSMISSIONS) * 2
    rec = ledger[0]
    assert {"transmission", "leaf", "dim", "sigma", "eps",
            "delta"} <= set(rec)
    assert rec["eps"] == pytest.approx(1.0)      # eps / 5 per transmission


# ------------------------------------ tree protocol: single-leaf parity

def _toy_problem(m=5, n=12, p=4, key=0):
    k = jax.random.PRNGKey(key)
    X = jax.random.normal(jax.random.fold_in(k, 0), (m, n, p))
    w = jnp.arange(1.0, p + 1)
    y = X @ w + 0.01 * jax.random.normal(jax.random.fold_in(k, 1), (m, n))
    return X, y, w

def test_protocol_tree_single_flat_leaf_byte_parity():
    """{'theta': flat} through the tree engine must be byte-identical to
    the flat array through the same engine — the safety invariant that
    lets one engine serve both the paper head and the model zoo."""
    X, y, _ = _toy_problem()
    cfg = TreeProtocolConfig(hist=3, lr=0.4, eps=2.0)
    theta0 = jnp.zeros(4)
    mask = jnp.arange(5) < 1

    def grad_flat(t, b):
        Xb, yb = b
        r = Xb @ t - yb
        return 0.5 * jnp.mean(r ** 2), Xb.T @ r / Xb.shape[0]

    def grad_tree(t, b):
        loss, g = grad_flat(t["theta"], b)
        return loss, {"theta": g}

    key = jax.random.PRNGKey(42)
    out_flat = protocol_tree_rounds(key, theta0, (X, y), grad_flat, cfg,
                                    byz_mask=mask, attack="scale", n=12)
    out_tree = protocol_tree_rounds(key, {"theta": theta0}, (X, y),
                                    grad_tree, cfg, byz_mask=mask,
                                    attack="scale", n=12)
    for name in ("theta_cq", "theta_os", "theta_qn"):
        a = np.asarray(getattr(out_flat, name))
        b = np.asarray(getattr(out_tree, name)["theta"])
        assert np.array_equal(a, b), name
    assert np.array_equal(np.asarray(out_flat.losses),
                          np.asarray(out_tree.losses))


def test_protocol_tree_trains_multi_leaf_under_attack():
    """The five-transmission engine fits a 2-leaf least-squares model
    through a Byzantine machine + DP noise; memory threads across steps
    and carries curvature."""
    m, n, p = 5, 40, 3
    k = jax.random.PRNGKey(5)
    X = jax.random.normal(jax.random.fold_in(k, 0), (m, n, p))
    w, b0 = jnp.array([1.0, -2.0, 0.5]), 0.7
    y = X @ w + b0

    def grad_fn(t, batch):
        Xb, yb = batch
        r = Xb @ t["w"] + t["b"] - yb
        loss = 0.5 * jnp.mean(r ** 2)
        return loss, {"w": Xb.T @ r / n, "b": jnp.mean(r, keepdims=True)}

    cfg = TreeProtocolConfig(hist=4, lr=0.5, eps=50.0)
    theta = {"w": jnp.zeros(p), "b": jnp.zeros(1)}
    mask = jnp.arange(m) < 1
    key = jax.random.PRNGKey(6)
    losses = []
    step = jax.jit(lambda key, t, mem: protocol_tree_rounds(
        key, t, (X, y), grad_fn, cfg, mem=mem, byz_mask=mask,
        attack="signflip", n=n))
    mem = bfgs.LBFGSMemory.init_like(cfg.hist, theta, machines=m)
    for i in range(25):
        key, sub = jax.random.split(key)
        out = step(sub, theta, mem)
        theta, mem = out.theta_qn, out.mem
        losses.append(float(out.losses.mean()))
    assert losses[-1] < 0.2 * losses[0]
    assert int(mem.count.max()) > 0              # curvature pairs landed


@pytest.mark.parametrize("eps", [0.0, 50.0])
def test_protocol_tree_step_keeps_param_dtypes(eps):
    """A bf16/f32 parameter tree (the full-width model zoo) comes back in
    its own dtypes, so the next step reuses the compiled executable."""
    m, n = 4, 16
    X = jax.random.normal(jax.random.PRNGKey(8), (m, n, 3))
    y = X @ jnp.array([1.0, -1.0, 0.5])

    def grad_fn(t, batch):
        Xb, yb = batch
        r = Xb @ t["w"].astype(jnp.float32) + t["b"] - yb
        return 0.5 * jnp.mean(r ** 2), {
            "w": (Xb.T @ r / n).astype(t["w"].dtype),
            "b": jnp.mean(r, keepdims=True)}

    cfg = TreeProtocolConfig(hist=2, lr=0.5, eps=eps)
    theta = {"w": jnp.zeros(3, jnp.bfloat16), "b": jnp.zeros(1)}
    traces = []

    def step(key, t, mem):
        traces.append(1)
        return protocol_tree_rounds(key, t, (X, y), grad_fn, cfg, mem=mem,
                                    n=n)
    step = jax.jit(step)
    mem = bfgs.LBFGSMemory.init_like(cfg.hist, theta, machines=m)
    for i in range(2):
        out = step(jax.random.PRNGKey(i), theta, mem)
        theta, mem = out.theta_qn, out.mem
    assert {k: v.dtype for k, v in theta.items()} == {
        "w": jnp.bfloat16, "b": jnp.float32}
    assert len(traces) == 1


# -------------------------------------------- golden byte parity (smoke)

@pytest.mark.slow
def test_smoke_preset_matches_pre_refactor_golden():
    """The refactored wire path must reproduce the pre-refactor smoke
    artifact BYTE-EXACTLY per key: metrics and per-replicate theta_qn."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    scenarios = build_preset("smoke")
    art = SweepExecutor().run(scenarios, store_thetas=True)
    assert set(art["scenarios"]) == set(golden)
    for sid, want in golden.items():
        got = art["scenarios"][sid]
        assert got["metrics"] == want["metrics"], sid
        assert got["thetas_qn"] == want["thetas_qn"], sid


# ------------------------------------------- zoo scenarios (fast checks)

def test_train_scenario_roundtrip_and_fast_variant():
    from repro.sweep.grid import scenario_from_json
    from repro.sweep.presets import fast_variant, zoo_smoke_scenarios
    s = TrainScenario(arch="glm4-9b", steps=7, eps=5.0, byz_frac=0.25,
                      attack="signflip")
    back = scenario_from_json(json.loads(json.dumps(s.to_json())))
    assert back == s                       # artifact resume round-trip
    assert s.to_json()["kind"] == "train"
    fast = fast_variant([s], reps=2)[0]
    assert fast.steps == 2 and fast.arch == s.arch
    scens = zoo_smoke_scenarios()
    families = {sc.arch for sc in scens}
    assert len(families) == 4              # one reduced config per family
    assert len({sc.scenario_id() for sc in scens}) == len(scens)
    with pytest.raises(ValueError):
        TrainScenario(arch="not-a-model")
    with pytest.raises(ValueError):
        TrainScenario(batch=5, machines=4)


def test_train_launcher_exposes_registry_aggregators():
    """The launcher's ACTUAL parser accepts every registered aggregator
    (qn path included — ``dcq_mad`` is the wire default) and rejects
    typos; both optimizer names parse."""
    from repro.agg import registered
    from repro.launch.train import build_parser
    ap = build_parser()
    for name in registered():
        assert ap.parse_args(["--agg", name]).agg == name
    with pytest.raises(SystemExit):
        ap.parse_args(["--agg", "typo"])
    assert ap.parse_args(["--optimizer", "qn"]).optimizer == "qn"
    assert ap.parse_args(["--config", "glm4-9b"]).arch == "glm4-9b"


def test_launcher_compile_cache_and_machine_mesh(monkeypatch):
    """Entry points keep the compile cache where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path in the checkout; machine meshes have
    Auto axes (jax.make_mesh's default Explicit axes break X[0])."""
    from jax.sharding import AxisType

    from repro.launch.cli import checkout_root, enable_compile_cache
    from repro.launch.mesh import make_machine_mesh
    repo = Path(__file__).resolve().parents[1]
    assert checkout_root() == repo
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == old   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    assert make_machine_mesh(1).axis_types == (AxisType.Auto,)


# --------------------------------------------- zoo training compile-once

@pytest.mark.slow
def test_zoo_group_compiles_once_and_records_per_leaf_spend():
    """Two DP budgets of one zoo group ride ONE compiled train step
    (sigmas are traced), and the artifact records carry the per-leaf
    spend ledger + the train comm record."""
    common = dict(arch="xlstm-125m", steps=2, batch=4, seq=8, machines=2,
                  aggregator="dcq_mad", attack="signflip", byz_frac=0.5,
                  lr=0.3)
    s1 = TrainScenario(eps=5.0, **common)
    s2 = TrainScenario(eps=50.0, **common)
    assert s1.group_key() == s2.group_key()
    assert s1.scenario_id() != s2.scenario_id()
    ex = SweepExecutor()
    art = ex.run([s1, s2])
    assert ex.trace_counts[s1.group_key()] == 1  # compile-once: 2
    #                                              scenarios x 2 steps
    for s in (s1, s2):
        rec = art["scenarios"][s.scenario_id()]
        assert {"scenario", "metrics", "spend", "comm",
                "timing"} <= set(rec)
        assert rec["scenario"]["kind"] == "train"
        assert len(rec["metrics"]["losses"]) == 2
        assert rec["spend"]["per_leaf"], "per-leaf ledger missing"
        leaves = {r["leaf"] for r in rec["spend"]["per_leaf"]}
        assert len(leaves) > 1                   # one entry per leaf
        assert rec["comm"]["bytes_per_machine"] == \
            5 * rec["comm"]["bytes_per_round"]
    # different budgets -> different per-leaf sigmas in the ledger
    sig1 = art["scenarios"][s1.scenario_id()]["spend"]["sigmas"]
    sig2 = art["scenarios"][s2.scenario_id()]["spend"]["sigmas"]
    assert all(a > b for a, b in zip(sig1, sig2))


# ------------------------------------------------------- example driver

@pytest.mark.slow
def test_robust_llm_training_example_runs():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "examples",
                        "robust_llm_training.py")
    spec = importlib.util.spec_from_file_location("robust_llm_training",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    params, mem, losses = mod.run(steps=2, batch=4, seq=8, machines=2,
                                  aggregator="dcq_mad", attack="signflip",
                                  byz_frac=0.5, log_every=10)
    assert len(losses) == 2
    assert all(np.isfinite(v) for v in losses)
    assert tree_dot(params, params) > 0
