"""The ``repro.api`` stability contract.

``repro.api`` is the repository's public surface: the snapshot below is
the promise. Extending it is fine (add the name HERE too); renaming or
removing anything, or breaking an entry-point signature, fails this
test and therefore CI — that is the point.
"""
import inspect

import jax
import jax.numpy as jnp
import pytest

import repro.api as api

# -- the public-surface snapshot: edit deliberately, never incidentally --
API_SNAPSHOT = {
    # entry points
    "run_protocol", "run_monte_carlo", "run_sweep", "serve",
    # registry views
    "registered_aggregators", "registered_attacks",
    # the types those entry points consume / return
    "ProtocolConfig", "ProtocolResult", "DPQNProtocol",
    "MEstimationProblem", "get_problem",
    "AggregationService", "ServeConfig", "FlushPolicy", "RingBuffer",
}

# every keyword a signature promises; positional order is part of it for
# the leading data arguments.
SIGNATURES = {
    "run_protocol": ["X", "y", "problem", "cfg", "key", "seed"],
    "run_monte_carlo": ["X", "y", "reps", "problem", "cfg", "keys", "seed"],
    "run_sweep": ["scenarios", "fast", "artifact_path"],
    "serve": ["theta", "cfg", "policy", "sharding"],
}


def test_public_surface_snapshot():
    assert set(api.__all__) == API_SNAPSHOT
    missing = [n for n in api.__all__ if not hasattr(api, n)]
    assert not missing, f"__all__ names missing from module: {missing}"


def test_entry_point_signatures_stable():
    for name, params in SIGNATURES.items():
        sig = inspect.signature(getattr(api, name))
        got = [p for p in sig.parameters
               if sig.parameters[p].kind is not inspect.Parameter.VAR_KEYWORD]
        assert got == params, f"{name} signature drifted: {got}"


def test_registry_views():
    aggs = api.registered_aggregators()
    assert {"mean", "median", "trimmed", "geomedian", "dcq",
            "dcq_mad"} <= set(aggs)
    assert {"none", "scale", "signflip"} <= set(api.registered_attacks())


def test_serve_facade_runs():
    svc = api.serve(jnp.zeros(4), method="median", capacity=6)
    svc.submit_many(jax.random.normal(jax.random.PRNGKey(0), (6, 4)))
    assert svc.round_idx == 1
    # cfg and field kwargs are mutually exclusive
    with pytest.raises(ValueError):
        api.serve(jnp.zeros(4), cfg=api.ServeConfig(), method="median")


def test_run_protocol_facade():
    from repro.data.synthetic import make_shards
    X, y = make_shards(jax.random.PRNGKey(0), "logistic", 6, 40, 4)
    res = api.run_protocol(X, y, cfg=api.ProtocolConfig(noiseless=True))
    assert res.theta_qn.shape == (4,)
    arr = api.run_monte_carlo(X, y, reps=2,
                              cfg=api.ProtocolConfig(noiseless=True))
    assert arr.theta_qn.shape == (2, 4)


# -- the facade keeps one DPQNProtocol per equal (problem, cfg) ----------

KEYS = jax.random.split(jax.random.PRNGKey(1), 3)


@pytest.fixture
def facade():
    api._protocols.clear()
    yield
    api._protocols.clear()


@pytest.fixture(scope="module")
def logistic():
    from repro.data.synthetic import make_shards
    return make_shards(jax.random.PRNGKey(0), "logistic", 6, 1000, 4)


@pytest.fixture(scope="module")
def linear():
    from repro.data.synthetic import make_shards
    return make_shards(jax.random.PRNGKey(0), "linear", 6, 1000, 4)


def _theta(data, problem, cfg):
    X, y = data
    return api.run_monte_carlo(X, y, problem=problem, cfg=cfg,
                               keys=KEYS).theta_qn


# entry point -> (facade call, the same call on a fresh DPQNProtocol)
ENTRIES = {
    "run_monte_carlo": (
        lambda X, y, cfg: api.run_monte_carlo(X, y, cfg=cfg, keys=KEYS),
        lambda proto, X, y: proto.run_monte_carlo(KEYS, X, y)),
    "run_protocol": (
        lambda X, y, cfg: api.run_protocol(X, y, cfg=cfg,
                                           key=KEYS[0]).arrays,
        lambda proto, X, y: proto.run(KEYS[0], X, y).arrays),
}


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_equal_problem_and_config_reuse_one_engine(facade, logistic,
                                                   entry):
    call, fresh_call = ENTRIES[entry]
    X, y = logistic
    cfg_a, cfg_b = api.ProtocolConfig(eps=10.0), api.ProtocolConfig(eps=10.0)
    assert cfg_a is not cfg_b
    first, second = call(X, y, cfg_a), call(X, y, cfg_b)
    (proto,) = api._protocols.values()
    assert api._protocol("logistic", cfg_b) is proto
    assert len(proto._engines) == 1 and proto.trace_count == 1
    fresh = fresh_call(api.DPQNProtocol(api.get_problem("logistic"), cfg_a),
                       X, y)
    for got in (first, second):
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(fresh)):
            assert jnp.array_equal(a, b)


@pytest.mark.parametrize("change", ["eps", "huber_c", "huber_c_in_place"])
def test_a_changed_problem_or_config_builds_a_new_engine(facade, linear,
                                                         change):
    import dataclasses
    from repro.core.losses import HuberRegression
    cfg = api.ProtocolConfig(eps=10.0)
    prob = HuberRegression(c=1.0)
    first = _theta(linear, prob, cfg)
    if change == "eps":
        cfg = dataclasses.replace(cfg, eps=20.0)
    elif change == "huber_c":
        prob = HuberRegression(c=2.0)
    else:
        prob.c = 2.0
    second = _theta(linear, prob, cfg)
    assert len(api._protocols) == 2
    assert bool(jnp.isfinite(first).all() & jnp.isfinite(second).all())
    assert not jnp.array_equal(first, second)
    # the first engine is kept under the values it was built with
    kept = api._protocol(HuberRegression(c=1.0), api.ProtocolConfig(eps=10.0))
    assert kept.problem.c == 1.0 and kept.trace_count == 1
    assert len(api._protocols) == 2


def test_a_problem_that_does_not_hash_runs_unkept(facade, linear):
    from repro.core.losses import HuberRegression
    prob = HuberRegression(c=jnp.asarray(1.0))
    got = _theta(linear, prob, api.ProtocolConfig(eps=10.0))
    assert got.shape == (len(KEYS), 4) and bool(jnp.isfinite(got).all())
    assert not api._protocols
    assert api._protocol(prob, None) is not api._protocol(prob, None)


def test_a_changed_matmul_precision_retraces_the_kept_engine(facade,
                                                             logistic):
    cfg = api.ProtocolConfig(eps=10.0)
    for precision in ("highest", "bfloat16", "highest"):
        with jax.default_matmul_precision(precision):
            jax.block_until_ready(_theta(logistic, "logistic", cfg))
    (proto,) = api._protocols.values()
    assert proto.trace_count == 2


@pytest.mark.parametrize("public_call", [
    "dispatch.clear_cache", "dispatch.set_table", "agg.register",
    "attacks.register", "privacy.register"])
def test_state_the_trace_reads_changed_builds_anew(facade, public_call):
    from repro import agg, attacks, privacy
    from repro.agg import dispatch
    change = {
        "dispatch.clear_cache": dispatch.clear_cache,
        "dispatch.set_table": lambda: dispatch.set_table(None, "no-such"),
        "agg.register": lambda: agg.register(agg.get_aggregator("median")),
        "attacks.register": lambda: attacks.register(
            attacks.get_attack("scale")),
        "privacy.register": lambda: privacy.register(
            privacy.get_accountant("basic")),
    }[public_call]
    cfg = api.ProtocolConfig()
    before = api._protocol("logistic", cfg)
    assert api._protocol("logistic", cfg) is before
    change()
    assert api._protocol("logistic", cfg) is not before


def test_the_kept_protocols_stay_within_their_bound(facade):
    n = api._PROTOCOLS_KEPT
    for eps in range(1, n + 1):
        api._protocol("logistic", api.ProtocolConfig(eps=float(eps)))
    oldest = api._protocol("logistic", api.ProtocolConfig(eps=1.0))  # used
    api._protocol("logistic", api.ProtocolConfig(eps=float(n + 1)))
    assert len(api._protocols) == n
    kept = {key[1].eps for key in api._protocols}
    assert 2.0 not in kept and {1.0, float(n + 1)} <= kept
    assert api._protocol("logistic", api.ProtocolConfig(eps=1.0)) is oldest


def test_threads_share_the_kept_protocols(facade):
    """Threads looking protocols up at once, with a switch interval short
    enough to interleave the lookup, the insert and the eviction: no
    lookup fails, the bound holds, and while nothing is evicted equal
    keys get one protocol."""
    import sys
    import threading
    n = api._PROTOCOLS_KEPT
    few = [api.ProtocolConfig(eps=float(e)) for e in range(1, 9)]
    many = [api.ProtocolConfig(eps=float(e)) for e in range(1, 2 * n)]
    got, errors = [], []

    def work(cfgs):
        try:
            for _ in range(60):
                for cfg in cfgs:
                    got.append((cfg.eps, api._protocol("logistic", cfg)))
        except Exception as e:        # pragma: no cover - the failure
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for cfgs in (few, many):
            got.clear()
            threads = [threading.Thread(target=work, args=(cfgs,))
                       for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert not errors
            assert len(api._protocols) <= n
            if cfgs is few:
                by_eps = {}
                for eps, proto in got:
                    assert by_eps.setdefault(eps, proto) is proto
    finally:
        sys.setswitchinterval(interval)
