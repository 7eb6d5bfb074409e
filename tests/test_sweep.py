"""Scenario-sweep engine: grid expansion, jit-group keying (compile
counters), artifact schema round-trip, resume-from-partial, and
sweep-vs-direct Monte-Carlo agreement."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DPQNProtocol, get_problem
from repro.sweep import artifact as artifact_mod
from repro.sweep import (Scenario, ScenarioGrid, SweepExecutor,
                         build_preset, fast_variant, group_scenarios,
                         run_scenarios, scenario_from_json, smoke_scenarios)

M, N, P = 6, 400, 4


def tiny(eps=20.0, **kw):
    base = dict(problem="logistic", m=M, n=N, p=P, eps=eps, delta=0.05,
                reps=2, data_seed=0)
    base.update(kw)
    return Scenario(**base)


# ------------------------------------------------------------------- grid

def test_grid_expansion_counts():
    grid = ScenarioGrid(problems=("logistic", "poisson"),
                        attacks=("scale", "signflip", "none"),
                        aggregators=("dcq", "median"),
                        eps_grid=(10.0, 30.0),
                        m_grid=(6, 12), byz_fracs=(0.0, 0.1))
    scens = grid.expand()
    assert grid.size() == len(scens) == 2 * 3 * 2 * 2 * 2 * 2
    assert len({s.scenario_id() for s in scens}) == len(scens)


def test_grouping_splits_static_merges_dynamic():
    """eps / byz_frac / attack_factor / seeds ride the vmap axis of one
    group; loss, attack, aggregator, trust, shapes split groups."""
    grid = ScenarioGrid(problems=("logistic", "poisson"),
                        attacks=("scale", "signflip"),
                        eps_grid=(10.0, 30.0), byz_fracs=(0.0, 0.1),
                        m_grid=(6,), n=N, p=P, reps=2)
    groups = group_scenarios(grid.expand())
    assert len(groups) == 4                      # 2 losses x 2 attacks
    assert all(len(v) == 4 for v in groups.values())   # 2 eps x 2 byz
    # static field split: different aggregator -> different group
    a = tiny(aggregator="dcq")
    b = tiny(aggregator="median")
    assert a.group_key() != b.group_key()
    # dynamic field merge: different eps/byz/data_seed -> same group
    assert tiny(eps=4.0).group_key() == tiny(eps=50.0).group_key()
    assert tiny(byz_frac=0.5).group_key() == tiny().group_key()
    assert tiny(data_seed=7).group_key() == tiny().group_key()


def test_smoke_preset_shape():
    """Acceptance: >=8 scenarios covering >=2 losses x >=2 attacks x
    >=2 aggregators, and every jit group batches >1 scenario."""
    scens = smoke_scenarios()
    assert len(scens) >= 8
    assert len({s.problem for s in scens}) >= 2
    assert len({s.attack for s in scens}) >= 2
    assert len({s.aggregator for s in scens}) >= 2
    # one registry-path group (omniscient alie x dcq) rides the CI grid,
    # so every PR compiles and executes the repro.attacks dispatch
    assert any(s.attack == "alie" and s.aggregator == "dcq"
               for s in scens)
    groups = group_scenarios(scens)
    assert all(len(v) >= 2 for v in groups.values())


def test_scenario_json_round_trip():
    s = tiny(byz_frac=0.1, rep_seeds=(3, 4), gammas=(0.5,) * 5)
    restored = scenario_from_json(json.loads(json.dumps(s.to_json())))
    assert restored == s
    assert restored.scenario_id() == s.scenario_id()


def test_scenario_validation():
    with pytest.raises(ValueError, match="rep_seeds"):
        tiny(reps=3, rep_seeds=(1, 2))
    with pytest.raises(ValueError, match="pair"):
        tiny(dataset="digits")
    with pytest.raises(KeyError, match="unknown preset"):
        build_preset("nope")


def test_fast_variant_truncates_reps_and_seeds():
    scens = [tiny(reps=4, rep_seeds=(1, 2, 3, 4)), tiny(reps=1,
                                                        rep_seeds=(9,))]
    fast = fast_variant(scens, reps=2)
    assert fast[0].reps == 2 and fast[0].rep_seeds == (1, 2)
    assert fast[1].reps == 1 and fast[1].rep_seeds == (9,)


# --------------------------------------------------------------- executor

@pytest.fixture(scope="module")
def two_eps_artifact():
    """A 2-point eps grid through one executor, reused across tests."""
    executor = SweepExecutor()
    scens = [tiny(eps=20.0, rep_seeds=(0, 1)), tiny(eps=40.0,
                                                    rep_seeds=(2, 3))]
    art = executor.run(scens)
    return executor, scens, art


def test_one_compile_per_jit_group(two_eps_artifact):
    """The compile-counter contract: a whole group traces exactly once,
    and a SECOND run over the same group does not retrace."""
    executor, scens, _ = two_eps_artifact
    (gkey,) = {s.group_key() for s in scens}
    assert executor.trace_counts[gkey] == 1
    executor.run([tiny(eps=50.0, rep_seeds=(7, 8)),
                  tiny(eps=4.0, byz_frac=1 / M, rep_seeds=(5, 6))])
    assert executor.trace_counts[gkey] == 1      # cache hit, no retrace


def test_sweep_matches_direct_monte_carlo(two_eps_artifact):
    """Sweep-engine results agree with direct run_monte_carlo per key to
    1e-5 on a 2-point grid (host-calibrated sigma_base keeps the noise
    draws identical to the compile-once static path)."""
    _, scens, art = two_eps_artifact
    X, y = __import__("repro.data.synthetic", fromlist=["make_shards"]
                      ).make_shards(jax.random.PRNGKey(0), "logistic",
                                    M, N, P)
    prob = get_problem("logistic")
    for s in scens:
        proto = DPQNProtocol(prob, s.protocol_config())
        keys = jnp.stack([jax.random.PRNGKey(k) for k in s.rep_seeds])
        direct = proto.run_monte_carlo(keys, X, y)
        rec = art["scenarios"][s.scenario_id()]
        np.testing.assert_allclose(
            np.asarray(rec["thetas_qn"], np.float32),
            np.asarray(direct.theta_qn), atol=1e-5,
            err_msg=f"eps={s.eps}")
        from repro.core import monte_carlo_mrse
        from repro.data.synthetic import target_theta
        assert rec["metrics"]["mrse_qn"] == pytest.approx(
            monte_carlo_mrse(direct.theta_qn, target_theta(P)), abs=1e-5)


def test_spend_ledger_recorded(two_eps_artifact):
    _, scens, art = two_eps_artifact
    for s in scens:
        spend = art["scenarios"][s.scenario_id()]["spend"]
        assert spend["eps_total"] == s.eps
        assert spend["n_transmissions"] == 5
        assert spend["eps_per_round"] == pytest.approx(s.eps / 5)
        assert len(spend["sigmas"]) == 5
        assert all(v >= 0 for v in spend["sigmas"])


def test_mixed_attack_grid_compiles_once_per_group():
    executor = SweepExecutor()
    grid = ScenarioGrid(problems=("logistic",),
                        attacks=("scale", "signflip"),
                        eps_grid=(10.0, 30.0), m_grid=(M,), n=N, p=P,
                        reps=2, byz_fracs=(1 / M,))
    executor.run(grid.expand())
    assert len(executor.trace_counts) == 2       # one per attack
    assert all(c == 1 for c in executor.trace_counts.values())


def test_untrusted_center_scenarios_run():
    art = run_scenarios([tiny(center_trust="untrusted", eps=20.0),
                         tiny(center_trust="untrusted", eps=40.0)])
    for rec in art["scenarios"].values():
        assert rec["spend"]["n_transmissions"] == 6
        assert len(rec["spend"]["sigmas"]) == 6
        # untrusted mode transmits SIX p-vectors; the comm record tracks it
        assert rec["comm"]["n_transmissions"] == 6
        assert rec["comm"]["bytes_per_machine"] == 4 * 6 * P


def test_untrusted_preset_driven_by_registry():
    """The untrusted preset sweeps center_trust x EVERY registered
    aggregator — a new registry entry appears in the grid automatically."""
    from repro.agg import registered
    from repro.sweep import untrusted_scenarios
    scens = untrusted_scenarios()
    assert {s.aggregator for s in scens} == set(registered())
    assert {s.center_trust for s in scens} == {"trusted", "untrusted"}
    groups = group_scenarios(scens)
    assert len(groups) == 2 * len(registered())   # one per (agg, trust)


# --------------------------------------------------------------- chunking

def test_chunked_group_matches_unchunked(two_eps_artifact):
    """chunk_size bounds replicates-per-launch; per-key results match the
    one-batch path (up to compiled-batch-shape fp reassociation) and the
    group still compiles exactly once (padded final chunk)."""
    _, scens, art = two_eps_artifact
    chunked = SweepExecutor(chunk_size=1)
    art_c = chunked.run(scens)
    (gkey,) = {s.group_key() for s in scens}
    assert chunked.trace_counts[gkey] == 1
    for s in scens:
        a = np.asarray(art["scenarios"][s.scenario_id()]["thetas_qn"])
        b = np.asarray(art_c["scenarios"][s.scenario_id()]["thetas_qn"])
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        t = art_c["scenarios"][s.scenario_id()]["timing"]
        assert t["n_chunks"] == 2 and t["group_size"] == 1


def test_chunked_writes_artifact_per_chunk(tmp_path, monkeypatch):
    """The artifact lands on disk after EVERY chunk (resumable mid-group),
    each snapshot schema-valid."""
    path = str(tmp_path / "chunked.json")
    saves = []
    real_save = artifact_mod.save

    def counting_save(art, p):
        real_save(art, p)
        saves.append(len(art["scenarios"]))
    monkeypatch.setattr(artifact_mod, "save", counting_save)
    scens = [tiny(eps=float(e), rep_seeds=(e, e + 1)) for e in (10, 20, 30)]
    SweepExecutor(chunk_size=2).run(scens, artifact_path=path)
    assert saves == [2, 3]          # chunk 1 (2 scens), chunk 2 (1 scen)
    artifact_mod.validate(artifact_mod.load(path))


def test_chunk_size_validation():
    with pytest.raises(ValueError, match="chunk_size"):
        SweepExecutor(chunk_size=0)


# ------------------------------------------------------------ comm record

def test_comm_record_rides_artifact(two_eps_artifact):
    """Schema v2: transmission cost rides the same record as MRSE."""
    _, scens, art = two_eps_artifact
    for s in scens:
        comm = art["scenarios"][s.scenario_id()]["comm"]
        assert comm["bytes_per_round"] == 4 * P
        assert comm["bytes_per_machine"] == 4 * 5 * P
        assert comm["n_transmissions"] == 5
        assert comm["eps_per_round"] == pytest.approx(s.eps / 5)
        # the paper's budget argument: Newton's Hessian round dwarfs qN
        assert comm["newton_bytes_per_machine"] > comm["bytes_per_machine"]


def test_artifact_v3_rejects_missing_comm_and_accountant(two_eps_artifact):
    _, _, art = two_eps_artifact
    import json as _json
    bad = _json.loads(_json.dumps(art))
    next(iter(bad["scenarios"].values())).pop("comm")
    with pytest.raises(ValueError, match="missing 'comm'"):
        artifact_mod.validate(bad)
    bad = _json.loads(_json.dumps(art))
    next(iter(bad["scenarios"].values()))["spend"].pop("accountant")
    with pytest.raises(ValueError, match="missing 'accountant'"):
        artifact_mod.validate(bad)
    assert art["schema_version"] == 3
    # a v2 artifact (pre-accountant) fails validation, so resume restarts
    bad = _json.loads(_json.dumps(art))
    bad["schema_version"] = 2
    with pytest.raises(ValueError, match="schema_version"):
        artifact_mod.validate(bad)
    # flat rows expose the byte + accounting columns for plotting
    row = artifact_mod.rows(art)[0]
    assert "bytes_per_machine" in row and "bytes_per_round" in row
    assert row["accountant"] == "basic"
    assert row["sigma_ratio_vs_basic"] == 1.0


# --------------------------------------------------------------- artifact

def test_artifact_round_trip(tmp_path, two_eps_artifact):
    _, _, art = two_eps_artifact
    path = tmp_path / "sweep.json"
    artifact_mod.save(art, str(path))
    loaded = artifact_mod.load(str(path))
    assert loaded == json.loads(json.dumps(art))   # JSON-faithful
    artifact_mod.validate(loaded)
    csv_path = tmp_path / "sweep.csv"
    artifact_mod.to_csv(loaded, str(csv_path))
    lines = csv_path.read_text().strip().splitlines()
    assert len(lines) == 1 + len(art["scenarios"])
    assert "mrse_qn" in lines[0] and "eps_total" in lines[0]


def test_artifact_validation_rejects_bad_schema(two_eps_artifact):
    _, _, art = two_eps_artifact
    bad = json.loads(json.dumps(art))
    bad["schema_version"] = 999
    with pytest.raises(ValueError, match="schema_version"):
        artifact_mod.validate(bad)
    bad = json.loads(json.dumps(art))
    next(iter(bad["scenarios"].values())).pop("metrics")
    with pytest.raises(ValueError, match="missing 'metrics'"):
        artifact_mod.validate(bad)
    with pytest.raises(ValueError, match="kind"):
        artifact_mod.validate({"schema_version": 1})


def test_resume_from_partial(tmp_path):
    """An interrupted sweep resumes: completed scenarios are skipped (no
    retrace of their group), pending ones run, artifact ends complete."""
    path = str(tmp_path / "partial.json")
    a = tiny(eps=10.0, rep_seeds=(0, 1))
    b = tiny(eps=30.0, rep_seeds=(2, 3))
    c = tiny(eps=30.0, aggregator="median", rep_seeds=(4, 5))
    first = SweepExecutor()
    first.run([a], artifact_path=path)
    assert set(artifact_mod.load(path)["scenarios"]) == {a.scenario_id()}

    resumed = SweepExecutor()
    art = resumed.run([a, b, c], artifact_path=path, resume=True)
    assert set(art["scenarios"]) == {s.scenario_id() for s in (a, b, c)}
    # a's record survived verbatim from the partial artifact
    assert art["scenarios"][a.scenario_id()]["timing"]["group_size"] == 1
    # only b (dcq group) and c (median group) actually ran
    assert sorted(resumed.trace_counts.values()) == [1, 1]
    artifact_mod.validate(artifact_mod.load(path))
    # no-resume reruns everything
    fresh = SweepExecutor()
    fresh.run([a, b], artifact_path=path, resume=False)
    assert sum(fresh.trace_counts.values()) == 1   # one shared dcq group


def test_resume_reproduces_same_results(tmp_path):
    """Derived replicate keys are a pure function of the scenario, so a
    resumed run and a fresh run produce identical numbers."""
    s = tiny(eps=25.0)                # no explicit rep_seeds: derived keys
    art1 = run_scenarios([s])
    art2 = run_scenarios([s])
    np.testing.assert_array_equal(
        np.asarray(art1["scenarios"][s.scenario_id()]["thetas_qn"]),
        np.asarray(art2["scenarios"][s.scenario_id()]["thetas_qn"]))


# ---------------------------------------------------------------- sharded

def test_sharded_sweep_matches_single_host():
    """The sweep executor with a mesh routes every scenario through the
    shard_map machine map (dist/sharded_protocol.py) and agrees with the
    single-host executor. Runs in a subprocess with forced host devices
    (the main process must keep seeing one device)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pre = ("import os\n"
           "os.environ['XLA_FLAGS'] = "
           "'--xla_force_host_platform_device_count=4'\n"
           "import sys; sys.path.insert(0, 'src')\n")
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.launch.mesh import make_machine_mesh
        from repro.sweep import Scenario, SweepExecutor

        scens = [Scenario(problem="logistic", m=7, n=100, p=4, eps=e,
                          reps=2, noiseless=True) for e in (10.0, 30.0)]
        mesh = make_machine_mesh(4)
        sharded = SweepExecutor(mesh=mesh).run(scens)
        single = SweepExecutor().run(scens)
        for s in scens:
            a = np.asarray(sharded["scenarios"][s.scenario_id()]["thetas_qn"])
            b = np.asarray(single["scenarios"][s.scenario_id()]["thetas_qn"])
            np.testing.assert_allclose(a, b, atol=1e-5)
        print("SHARDED_OK", sharded["meta"]["n_devices"])
    """)
    out = subprocess.run([sys.executable, "-c", pre + code],
                         capture_output=True, text=True, timeout=600,
                         cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK 4" in out.stdout


def test_sharded_sweep_rejects_uneven_machines():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pre = ("import os\n"
           "os.environ['XLA_FLAGS'] = "
           "'--xla_force_host_platform_device_count=4'\n"
           "import sys; sys.path.insert(0, 'src')\n")
    code = textwrap.dedent("""
        from repro.launch.mesh import make_machine_mesh
        from repro.sweep import Scenario, SweepExecutor
        mesh = make_machine_mesh(4)
        try:
            SweepExecutor(mesh=mesh).run(
                [Scenario(m=5, n=50, p=3, reps=1, noiseless=True)])
        except ValueError as e:
            assert "shard evenly" in str(e), e
            print("UNEVEN_REJECTED")
    """)
    out = subprocess.run([sys.executable, "-c", pre + code],
                         capture_output=True, text=True, timeout=600,
                         cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "UNEVEN_REJECTED" in out.stdout


# ----------------------------------------------------------------- digits

def test_digits_scenario_metrics():
    scens = [Scenario(problem="logistic", dataset="digits", pair=(6, 9),
                      m=4, n=120, p=5, eps=e, gammas=(0.5,) * 5,
                      attack_factor=3.0, reps=2, data_seed=0)
             for e in (5.0, 30.0)]
    executor = SweepExecutor()
    art = executor.run(scens, store_thetas=False)
    assert all(c == 1 for c in executor.trace_counts.values())
    for s in scens:
        acc = art["scenarios"][s.scenario_id()]["metrics"]["accuracy"]
        assert 0.4 <= acc <= 1.0
    # more budget should not hurt a separable two-Gaussian problem much
    accs = [art["scenarios"][s.scenario_id()]["metrics"]["accuracy"]
            for s in scens]
    assert accs[1] >= accs[0] - 0.05
