"""Training loop: per-machine gradients -> DP noise -> robust aggregation
-> optimizer update. The paper's technique as a first-class feature.

The global batch is split into ``n_machines`` groups (the paper's node
machines = data-parallel ranks; on a mesh the machine axis is sharded over
pod x data). ``jax.vmap`` over the machine axis yields one gradient per
machine; dist/grad_agg.py then applies the Gaussian mechanism + Byzantine
simulation + the robust aggregator; the aggregate feeds a standard
optimizer. With ``method="mean"``/``sigma=0``/no attack this reduces to
ordinary data-parallel training (psum) — asserted in tests.

Activation memory: the block scan is rematerialised (jax.checkpoint), so
live activations are one layer's, per machine, per microbatch.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import TreeProtocolConfig
from repro.core.protocol import at_center, protocol_tree_rounds
from repro.dist.collectives import tree_machine_specs
from repro.dist.grad_agg import (GradAggConfig, robust_aggregate,
                                 spend_record)
from repro.dist.sharded_protocol import center_map
from repro.models.model import Model
from repro.train.optimizer import AdamW, apply_updates, global_norm


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    n_machines: int = 4
    microbatch: int = 0            # per-machine microbatch; 0 = whole batch
    remat: bool = True
    fsdp: bool = False             # ZeRO-style weight sharding over "data"
    grad_dtype: str = ""           # "" = native; "bfloat16" halves the
    #                                aggregation payload (§Perf knob)
    agg: GradAggConfig = dataclasses.field(
        default_factory=lambda: GradAggConfig(method="mean"))


def _split_machines(batch: Dict[str, jnp.ndarray], m: int):
    return jax.tree_util.tree_map(
        lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch)


def make_loss_fn(model: Model, remat: bool = True):
    def loss_fn(params, batch):
        loss, aux = model.loss(params, batch)
        return loss, aux
    return loss_fn


def make_train_step(model: Model, opt: AdamW, tcfg: TrainConfig,
                    mesh: Optional[Mesh] = None):
    """Returns train_step(params, opt_state, batch, key, byz_mask) ->
    (params, opt_state, metrics)."""
    loss_fn = make_loss_fn(model, tcfg.remat)
    m = tcfg.n_machines

    def machine_grad(params, mb):
        """Gradient of one machine's local loss (optionally microbatched)."""
        if tcfg.microbatch:
            B = mb["tokens"].shape[0]
            k = max(1, B // tcfg.microbatch)
            chunks = jax.tree_util.tree_map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), mb)

            def acc_step(carry, chunk):
                lsum, gsum = carry
                (lv, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, chunk)
                return (lsum + lv / k,
                        jax.tree_util.tree_map(
                            lambda a, b: a + b / k, gsum, g)), None
            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                acc_step, (jnp.zeros(()), zero), chunks)
            return loss, grads
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mb)
        return loss, grads

    def train_step(params, opt_state, batch, key,
                   byz_mask: Optional[jnp.ndarray] = None):
        mb = _split_machines(batch, m)
        losses, grads = jax.vmap(lambda b: machine_grad(params, b))(mb)
        if tcfg.grad_dtype:
            dt = jnp.dtype(tcfg.grad_dtype)
            grads = jax.tree_util.tree_map(lambda g: g.astype(dt), grads)
        machine_specs = None
        if mesh is not None:
            # machine axis on pod x data; payload dims keep the PARAM
            # sharding (collectives.tree_machine_specs — dropping it
            # replicates every machine's grad over the model axis, a 16x
            # blow-up; EXPERIMENTS.md §Perf HC-train it1).
            machine_specs = tree_machine_specs(grads, mesh, fsdp=tcfg.fsdp)
            grads = jax.lax.with_sharding_constraint(
                grads, jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), machine_specs))
        if tcfg.agg.strategy != "sharded":
            machine_specs = None
        agg = robust_aggregate(grads, tcfg.agg, key, byz_mask,
                               mesh=mesh, machine_specs=machine_specs)
        updates, opt_state = opt.update(agg, opt_state, params)
        params = apply_updates(params, updates)
        metrics = {"loss": losses.mean(),
                   "loss_per_machine": losses,
                   "grad_norm": global_norm(agg)}
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------- quasi-Newton (protocol)

@dataclasses.dataclass(frozen=True)
class QNTrainConfig:
    """Robust DP quasi-Newton training: every optimizer step IS one run of
    Algorithm 1's five transmissions over the parameter pytree."""
    n_machines: int = 4
    protocol: TreeProtocolConfig = dataclasses.field(
        default_factory=TreeProtocolConfig)
    attack: str = "none"           # repro.attacks registry name/alias
    attack_factor: float = -3.0
    remat: bool = True


def make_qn_train_step(model: Model, qcfg: QNTrainConfig,
                       mesh: Optional[Mesh] = None):
    """Returns train_step(params, mem, batch, key, byz_mask) ->
    (params, mem, metrics): one five-transmission protocol step
    (core.protocol.protocol_tree_rounds). The curvature state ``mem`` is
    the per-machine L-BFGS history from the SHARED core/bfgs.py
    implementation — the same two-loop the convex head uses, not a
    reimplementation — threaded through successive steps.

    ``n`` for the per-leaf DP calibration is the per-machine batch size
    (each batch row is one sample draw from the machine's shard).
    """
    loss_fn = make_loss_fn(model, qcfg.remat)
    m = qcfg.n_machines
    # on a mesh every device plays the center (the aggregation kernels
    # cannot be partitioned automatically)
    center = at_center if mesh is None else center_map(mesh)

    def grad_fn(params, mb):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mb)
        return loss, grads

    def train_step(params, mem, batch, key,
                   byz_mask: Optional[jnp.ndarray] = None):
        mb = _split_machines(batch, m)
        n = jax.tree_util.tree_leaves(mb)[0].shape[1]
        if mesh is not None:
            # machine axis over the mesh's batch axes, payload dims on the
            # param rules — GSPMD propagates these through all five rounds
            specs = tree_machine_specs(mb, mesh)
            mb = jax.lax.with_sharding_constraint(
                mb, jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), specs))
        out = protocol_tree_rounds(
            key, params, mb, grad_fn, qcfg.protocol, mem=mem,
            byz_mask=byz_mask, attack=qcfg.attack,
            attack_factor=qcfg.attack_factor, n=n, center=center)
        metrics = {"loss": out.losses.mean(),
                   "loss_per_machine": out.losses,
                   "grad_norm": out.grad_norm}
        return out.theta_qn, out.mem, metrics

    return train_step


class QNTrainer:
    """Protocol-driven loop: the model zoo trained by the SAME engine as
    the p=10 convex head — five DP transmissions, registry attacks and
    aggregators, per-leaf calibrated noise, L-BFGS curvature memory.
    ``trace_count`` counts traces of the step: one per compile."""

    def __init__(self, model: Model, qcfg: QNTrainConfig,
                 mesh: Optional[Mesh] = None):
        self.model, self.qcfg = model, qcfg
        self.trace_count = 0
        step = make_qn_train_step(model, qcfg, mesh)

        def counted(*args):
            self.trace_count += 1
            return step(*args)
        self.step_fn = jax.jit(counted)

    def init_memory(self, params):
        from repro.core.bfgs import LBFGSMemory
        return LBFGSMemory.init_like(self.qcfg.protocol.hist, params,
                                     machines=self.qcfg.n_machines)

    def fit(self, params, batches, key, byz_mask=None, log_every: int = 10,
            callback=None):
        mem = self.init_memory(params)
        history = []
        for i, batch in enumerate(batches):
            key, sub = jax.random.split(key)
            params, mem, metrics = self.step_fn(
                params, mem, batch, sub, byz_mask)
            if i % log_every == 0 or callback:
                history.append({"step": i, "loss": float(metrics["loss"])})
                if callback:
                    callback(i, metrics)
        return params, mem, history


class Trainer:
    """Convenience loop for the examples: synthetic LM data, logging."""

    def __init__(self, model: Model, opt: AdamW, tcfg: TrainConfig,
                 mesh: Optional[Mesh] = None):
        self.model, self.opt, self.tcfg = model, opt, tcfg
        self.step_fn = jax.jit(make_train_step(model, opt, tcfg, mesh))
        self.ledger = None  # populated by fit(): per-step DP spend records

    def fit(self, params, batches, key, byz_mask=None, log_every: int = 10,
            callback=None):
        opt_state = self.opt.init(params)
        # every step transmits one noised gradient pytree; the noise
        # config is static, so one per-step ledger entry covers them all
        # (basic composition: total spend = steps * per-step budget)
        per_step = spend_record(params, self.tcfg.agg, name="grad step")
        steps = 0
        history = []
        for i, batch in enumerate(batches):
            key, sub = jax.random.split(key)
            params, opt_state, metrics = self.step_fn(
                params, opt_state, batch, sub, byz_mask)
            steps = i + 1
            if i % log_every == 0 or callback:
                loss = float(metrics["loss"])
                history.append({"step": i, "loss": loss})
                if callback:
                    callback(i, metrics)
        eps = self.tcfg.agg.dp_eps
        self.ledger = {"per_step": per_step, "steps": steps,
                       "total_eps": steps * eps if eps > 0 else None}
        return params, opt_state, history
