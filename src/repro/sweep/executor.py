"""The sweep executor: one compiled executable per jit group.

Scenarios are bucketed by ``Scenario.group_key()`` (static config +
shapes). For each group the executor builds ONE function —

    jit(vmap_scenarios(vmap_replicates(protocol_rounds)))

— and pushes the whole group through it in a single call: the scenario
axis carries data, Byzantine masks, privacy budgets (as host-calibrated
``sigma_base`` rows, bit-identical to the compile-once static path), and
attack factors; the replicate axis carries PRNG keys. A grid over
eps x alpha x seeds therefore compiles once per (loss, attack, aggregator,
trust, shape) combination instead of once per point.

``trace_counts`` counts actual retraces per group; tests assert each group
compiles exactly one executable. Passing a ``mesh`` swaps the machine map
for the shard_map SPMD implementation (dist/sharded_protocol.py) and
shards every scenario's machine axis over the mesh — the sweep path and
the multi-device path are the same code.

Oversized jit groups are CHUNKED: with ``chunk_size=c`` a group larger
than ``c`` runs as ceil(len/c) batches of exactly ``c`` scenario rows
(the last chunk is padded by repeating its final scenario, so every chunk
reuses the single compiled executable — the compile-once contract holds),
bounding peak memory at ``c * reps`` replicates per launch. The artifact
is written atomically after every chunk, so an interrupted oversized
group resumes from its completed chunks.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import n_transmissions, protocol_rounds, vmap_machines
from repro.core.protocol import at_center, calibrate_sigma_base
from repro.sweep import artifact as artifact_mod
from repro.sweep.comm import comm_record
from repro.sweep.data import (build_data, byz_mask, compute_metrics,
                              replicate_keys)
from repro.sweep.grid import (Scenario, TrainScenario, group_label,
                              group_scenarios)


class SweepExecutor:
    """Runs scenario lists through per-jit-group compiled engines.

    One executor instance caches one engine per group key, so successive
    ``run`` calls (e.g. a resumed sweep in the same process) reuse compiled
    executables. ``trace_counts[group_key]`` is the number of times the
    group's engine was traced — the compile-counter contract is that it
    stays at 1 no matter how many scenarios or calls ride through it.
    """

    def __init__(self, mesh=None,
                 progress: Optional[Callable[[str], None]] = None,
                 chunk_size: Optional[int] = None):
        self.mesh = mesh
        if mesh is None:
            self._mmap, self._center = vmap_machines, at_center
        else:
            from repro.dist.sharded_protocol import center_map, machine_map
            self._mmap = machine_map(mesh, mesh.axis_names[0])
            self._center = center_map(mesh)
        self.progress = progress or (lambda msg: None)
        if chunk_size is not None and chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        self.chunk_size = chunk_size
        self.trace_counts: Dict[Tuple, int] = {}
        self._engines: Dict[Tuple, Callable] = {}
        self._data_cache: Dict[Tuple, Tuple] = {}

    # ------------------------------------------------------------- engines

    def _engine(self, scenario: Scenario) -> Callable:
        gkey = scenario.group_key()
        if gkey in self._engines:
            return self._engines[gkey]
        cfg = scenario.protocol_config()
        problem = _problem_for(scenario)
        attack = scenario.attack
        mmap, center = self._mmap, self._center
        self.trace_counts[gkey] = 0

        def one_rep(key, X, y, mask, eps, delta, factor, sigma_base):
            self.trace_counts[gkey] += 1
            return protocol_rounds(
                key, X, y, problem, cfg, byz_mask=mask, attack=attack,
                attack_factor=factor, eps=eps, delta=delta,
                sigma_base=sigma_base, machine_map=mmap, center=center)

        over_reps = jax.vmap(one_rep, in_axes=(0,) + (None,) * 7)
        over_scenarios = jax.vmap(over_reps, in_axes=(0,) * 8)
        engine = jax.jit(over_scenarios)
        self._engines[gkey] = engine
        return engine

    def _train_engine(self, scenario: TrainScenario):
        """One compiled protocol train STEP per zoo group: eps rides as
        traced per-leaf sigma trees, byz_frac as the mask, attack_factor
        as a traced scalar and the PRNG key per step — every scenario in
        the group (and every step of every scenario) reuses the single
        executable, extending the compile-once contract to training."""
        gkey = scenario.group_key()
        if gkey in self._engines:
            return self._engines[gkey]
        from repro.configs import get_config
        from repro.core.protocol import protocol_tree_rounds
        from repro.models.model import Model
        cfg = get_config(scenario.arch, reduced=True)
        model = Model(cfg, remat=True)
        tcfg = scenario.protocol_config()
        attack = scenario.attack
        mmap, center = self._mmap, self._center
        self.trace_counts[gkey] = 0

        def grad_fn(params, mb):
            (loss, _), g = jax.value_and_grad(
                model.loss, has_aux=True)(params, mb)
            return loss, g

        def step(key, params, mem, mb, mask, factor, sigmas):
            self.trace_counts[gkey] += 1
            return protocol_tree_rounds(
                key, params, mb, grad_fn, tcfg, mem=mem, byz_mask=mask,
                attack=attack, attack_factor=factor, sigmas=sigmas,
                machine_map=mmap, center=center)

        engine = (jax.jit(step), model, cfg)
        self._engines[gkey] = engine
        return engine

    def _run_train_group(self, gkey, scens: List[TrainScenario],
                         label: str) -> List[Dict]:
        """Run one zoo jit group scenario-by-scenario through its shared
        compiled step; returns one artifact record per scenario."""
        from repro.core import dp
        from repro.core.bfgs import LBFGSMemory
        from repro.core.transport import tree_size
        from repro.data.lm import make_batch
        from repro.train.trainer import _split_machines
        step_fn, model, cfg = self._train_engine(scens[0])
        records = []
        for s in scens:
            m = s.machines
            params = model.init(jax.random.PRNGKey(s.seed))
            mem = LBFGSMemory.init_like(s.hist, params, machines=m)
            mask = jnp.arange(m) < s.n_byzantine()
            if s.eps > 0:
                sigmas = jax.tree_util.tree_map(
                    lambda v: jnp.float32(v),
                    dp.calibrate_tree_sigmas(
                        params, s.n_per_machine(), s.eps, s.delta,
                        (s.gamma,) * 5, s.tail,
                        accountant=s.accountant))
            else:
                sigmas = {name: jnp.float32(0.0)
                          for name in dp.TREE_TRANSMISSIONS}
            # repro: allow(key-reuse) — historical derivation: every preset
            # artifact (and tests/golden/zoo_smoke.json) is byte-pinned to
            # these exact streams; new code uses repro.core.keys.stream_key.
            key = jax.random.PRNGKey(1000 + s.seed)
            # repro: allow(key-reuse) — same historical pin as above.
            data_key = jax.random.PRNGKey(s.seed + 1)
            t0 = time.perf_counter()
            losses, gnorm = [], 0.0
            for i in range(s.steps):
                batch = make_batch(jax.random.fold_in(data_key, i), cfg,
                                   s.batch, s.seq)
                mb = _split_machines(batch, m)
                if self.mesh is not None:
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as P
                    sharding = NamedSharding(
                        self.mesh, P(self.mesh.axis_names[0]))
                    mb = jax.tree_util.tree_map(
                        lambda x: jax.device_put(x, sharding), mb)
                key, sub = jax.random.split(key)
                out = step_fn(sub, params, mem, mb, mask,
                              jnp.float32(s.attack_factor), sigmas)
                params, mem = out.theta_qn, out.mem
                losses.append(float(out.losses.mean()))
                gnorm = float(out.grad_norm)
            dt = time.perf_counter() - t0
            p_total = tree_size(params)
            records.append({
                "scenario": s.to_json(),
                "metrics": {"loss_first": losses[0],
                            "loss_last": losses[-1],
                            "loss_drop": losses[0] - losses[-1],
                            "losses": losses,
                            "grad_norm_last": gnorm},
                "spend": _train_spend_record(s, params),
                "comm": {"n_transmissions": len(dp.TREE_TRANSMISSIONS),
                         "bytes_per_round": 4 * p_total,
                         "bytes_per_machine":
                             4 * p_total * len(dp.TREE_TRANSMISSIONS),
                         "n_params": p_total,
                         "eps_per_round":
                             s.eps / len(dp.TREE_TRANSMISSIONS),
                         "delta_per_round":
                             s.delta / len(dp.TREE_TRANSMISSIONS)},
                "thetas_qn": None,
                "timing": {"group": label,
                           "group_seconds": dt, "group_size": len(scens),
                           "steps": s.steps,
                           "traces": self.trace_counts[s.group_key()]},
            })
        return records

    # ------------------------------------------------------------- batching

    def _data_for(self, s: Scenario):
        """build_data memoized on the fields that determine the arrays —
        a fig-eps group's five budgets share one dataset, so the shards
        are built once, not once per scenario."""
        key = (s.dataset, s.problem, s.m, s.n, s.p, s.data_seed, s.pair)
        if key not in self._data_cache:
            self._data_cache[key] = build_data(s)
        return self._data_cache[key]

    def _batch_inputs(self, scens: List[Scenario]):
        """Stack the dynamic axes of one jit group. Every scenario gets its
        own data/mask/budget row; replicate keys ride the inner axis."""
        X_rows, y_rows, auxes = [], [], []
        for s in scens:
            X, y, aux = self._data_for(s)
            X_rows.append(X)
            y_rows.append(y)
            auxes.append(aux)
        X = jnp.stack(X_rows)
        y = jnp.stack(y_rows)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            axis = self.mesh.axis_names[0]
            n_dev = self.mesh.shape[axis]
            if X.shape[1] % n_dev:
                raise ValueError(
                    f"{X.shape[1]} machines do not shard evenly over "
                    f"{n_dev} devices on axis {axis!r}")
            spec = NamedSharding(self.mesh, P(None, axis))
            X = jax.device_put(X, spec)
            y = jax.device_put(y, spec)
        keys = jnp.stack([replicate_keys(s) for s in scens])
        masks = jnp.stack([byz_mask(s) for s in scens])
        eps = jnp.asarray([s.eps for s in scens], jnp.float32)
        delta = jnp.asarray([s.delta for s in scens], jnp.float32)
        factors = jnp.asarray([s.attack_factor for s in scens], jnp.float32)
        # float64 host calibration per scenario -> bit-parity with the
        # static compile-once path (see core/protocol.calibrate_sigma_base)
        sigma_rows = np.stack([
            np.asarray(calibrate_sigma_base(
                s.protocol_config(), s.p, s.n), np.float32)
            for s in scens])
        return (keys, X, y, masks, eps, delta, factors,
                jnp.asarray(sigma_rows)), auxes

    # ------------------------------------------------------------------ run

    def run(self, scenarios: Iterable[Scenario],
            artifact_path: Optional[str] = None, resume: bool = True,
            store_thetas: bool = True, meta: Optional[Dict] = None) -> Dict:
        """Execute scenarios group-by-group; returns the artifact dict.

        With ``artifact_path`` the artifact is written atomically after
        every jit group, and (when ``resume``) scenarios already present
        in a schema-valid artifact at that path are skipped.
        """
        scenarios = list(scenarios)
        art = artifact_mod.new_artifact(meta=_run_meta(meta))
        done: set = set()
        if artifact_path and resume:
            done = artifact_mod.load_done_ids(artifact_path)
            if done:
                art = artifact_mod.load(artifact_path)
                art["meta"].update(_run_meta(meta))
        pending = [s for s in scenarios
                   if s.scenario_id() not in done]
        skipped = len(scenarios) - len(pending)
        if skipped:
            self.progress(f"resume: {skipped} scenario(s) already in "
                          f"{artifact_path}, {len(pending)} to run")
        groups = group_scenarios(pending)
        for gi, (gkey, scens) in enumerate(groups.items()):
            label = group_label(gkey)
            if gkey[0] == "zoo":
                self.progress(f"[group {gi + 1}/{len(groups)}] {label}: "
                              f"{len(scens)} training run(s) x "
                              f"{scens[0].steps} step(s)")
                for s, record in zip(scens,
                                     self._run_train_group(gkey, scens,
                                                           label)):
                    art["scenarios"][s.scenario_id()] = record
                if artifact_path:
                    artifact_mod.save(art, artifact_path)
                continue
            chunks = self._chunks(scens)
            tag = (f" in {len(chunks)} chunk(s) of <= {self.chunk_size}"
                   if len(chunks) > 1 else "")
            self.progress(f"[group {gi + 1}/{len(groups)}] {label}: "
                          f"{len(scens)} scenario(s) x {scens[0].reps} reps"
                          f"{tag}")
            engine = self._engine(scens[0])
            for ci, chunk in enumerate(chunks):
                # pad split chunks to the fixed chunk_size by repeating the
                # last scenario: every chunk reuses the ONE compiled
                # executable (padded rows are dropped below).
                n_real = len(chunk)
                padded = chunk
                if len(chunks) > 1 and n_real < self.chunk_size:
                    padded = chunk + [chunk[-1]] * (self.chunk_size - n_real)
                inputs, auxes = self._batch_inputs(padded)
                t0 = time.perf_counter()
                arrs = engine(*inputs)
                jax.block_until_ready(arrs.theta_qn)
                dt = time.perf_counter() - t0
                for i, (s, aux) in enumerate(zip(chunk, auxes[:n_real])):
                    thetas = {"cq": arrs.theta_cq[i],
                              "os": arrs.theta_os[i],
                              "qn": arrs.theta_qn[i]}
                    record = {
                        "scenario": s.to_json(),
                        "metrics": compute_metrics(s, thetas, aux),
                        "spend": _spend_record(
                            s, np.asarray(arrs.sigmas[i, 0])),
                        "comm": comm_record(s.p, s.protocol_config()),
                        "thetas_qn": (np.asarray(arrs.theta_qn[i],
                                                 np.float64).tolist()
                                      if store_thetas else None),
                        "timing": {"group": label, "group_seconds": dt,
                                   "group_size": n_real,
                                   "chunk": ci, "n_chunks": len(chunks),
                                   "traces": self.trace_counts[gkey]},
                    }
                    art["scenarios"][s.scenario_id()] = record
                if artifact_path:
                    # per-chunk atomic write: an interrupted oversized
                    # group resumes from its completed chunks
                    artifact_mod.save(art, artifact_path)
        artifact_mod.validate(art)
        return art

    def _chunks(self, scens: List[Scenario]) -> List[List[Scenario]]:
        """Split one jit group into bounded scenario batches."""
        c = self.chunk_size
        if c is None or len(scens) <= c:
            return [scens]
        return [scens[i:i + c] for i in range(0, len(scens), c)]


def run_scenarios(scenarios: Iterable[Scenario], mesh=None,
                  artifact_path: Optional[str] = None, resume: bool = True,
                  store_thetas: bool = True, meta: Optional[Dict] = None,
                  progress: Optional[Callable[[str], None]] = None,
                  chunk_size: Optional[int] = None) -> Dict:
    """One-shot convenience wrapper around :class:`SweepExecutor`."""
    executor = SweepExecutor(mesh=mesh, progress=progress,
                             chunk_size=chunk_size)
    return executor.run(scenarios, artifact_path=artifact_path,
                        resume=resume, store_thetas=store_thetas, meta=meta)


# ---------------------------------------------------------------- internals

def _problem_for(scenario: Scenario):
    from repro.core import get_problem
    return get_problem(scenario.problem)


def _spend_record(s: Scenario, sigmas: np.ndarray) -> Dict:
    """Host-side exact privacy spend for the artifact (the traced ledger
    carries the same numbers as f32; the accountant math stays in float).

    Schema v3: the record names the accountant that certified the
    per-round budget, its sigma ratio vs basic composition, and the
    per-transmission sensitivity failure probabilities (nonzero for every
    transmission under the "subexp" high-probability accountant)."""
    from repro.core.protocol import _failure_probs
    from repro.privacy import get_accountant, multiplier_ratio
    cfg = s.protocol_config()
    k = n_transmissions(cfg)
    acct = get_accountant(s.accountant)
    eps_r, delta_r = acct.per_round(s.eps, s.delta, k)
    probs = _failure_probs(cfg, s.p, s.n)
    return {"eps_total": s.eps, "delta_total": s.delta,
            "n_transmissions": k, "eps_per_round": eps_r,
            "delta_per_round": delta_r,
            "sigmas": [float(v) for v in sigmas],
            "accountant": acct.name,
            "sigma_ratio_vs_basic":
                multiplier_ratio(s.accountant, s.eps, s.delta, k),
            "failure_probs": [float(f) for f in probs],
            "failure_prob_total": min(1.0, float(sum(probs)))}


def _train_spend_record(s: TrainScenario, params) -> Dict:
    """Per-STEP spend for one zoo training run, with the per-leaf ledger:
    every transmission's sigma at every leaf's own dimension (the per-leaf
    calibration made auditable, core.dp.tree_spend_ledger)."""
    from repro.core import dp
    from repro.privacy import get_accountant, multiplier_ratio
    k = len(dp.TREE_TRANSMISSIONS)
    if s.eps <= 0:
        return {"eps_total": 0.0, "delta_total": 0.0, "n_transmissions": k,
                "eps_per_round": 0.0, "delta_per_round": 0.0,
                "sigmas": [0.0] * k, "accountant": s.accountant,
                "sigma_ratio_vs_basic": 1.0, "per_leaf": []}
    acct = get_accountant(s.accountant)
    eps_r, delta_r = acct.per_round(s.eps, s.delta, k)
    ledger = dp.tree_spend_ledger(params, s.n_per_machine(), s.eps,
                                  s.delta, (s.gamma,) * 5, s.tail,
                                  accountant=s.accountant)
    sig_max = {name: max(r["sigma"] for r in ledger
                         if r["transmission"] == name)
               for name in dp.TREE_TRANSMISSIONS}
    return {"eps_total": s.eps, "delta_total": s.delta,
            "n_transmissions": k, "eps_per_round": eps_r,
            "delta_per_round": delta_r,
            "sigmas": [sig_max[name] for name in dp.TREE_TRANSMISSIONS],
            "accountant": acct.name,
            "sigma_ratio_vs_basic":
                multiplier_ratio(s.accountant, s.eps, s.delta, k),
            "per_leaf": ledger}


def _run_meta(meta: Optional[Dict]) -> Dict:
    out = {"jax": jax.__version__,
           "device": jax.devices()[0].platform,
           "n_devices": jax.device_count()}
    out.update(meta or {})
    return out
