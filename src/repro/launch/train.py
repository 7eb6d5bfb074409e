"""Training launcher: robust-DP training of any model-zoo --config.

CPU-scale entry point (reduced configs train for real; full configs only
lower — use launch/dryrun.py for those). Two optimizer paths share the
wire layer (core/transport.py):

  * ``--optimizer adamw`` (default): per-machine gradients -> attack ->
    DP noise -> robust aggregation -> AdamW (train/trainer.Trainer);
  * ``--optimizer qn``: every step IS one run of the paper's Algorithm 1
    over the parameter pytree — five DP transmissions, per-leaf
    calibrated noise, shared L-BFGS curvature (train/trainer.QNTrainer).

  python -m repro.launch.train --config xlstm-125m --steps 50 \
      --optimizer qn --eps 10 --byzantine 0.25 --attack signflip

``--sharded`` places the machine axis over all visible devices (pair
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.agg import registered as registered_aggregators
from repro.attacks import ALIASES as ATTACK_ALIASES
from repro.attacks import registered as registered_attacks
from repro.checkpoint import checkpoint
from repro.configs import get_config
from repro.core.keys import stream_key
from repro.configs.base import TreeProtocolConfig
from repro.data.lm import synthetic_lm_batches
from repro.dist.grad_agg import GradAggConfig
from repro.launch.cli import (add_common_flags, enable_compile_cache,
                              machine_mesh)
from repro.models.model import Model
from repro.train.optimizer import AdamW
from repro.train.trainer import (QNTrainConfig, QNTrainer, TrainConfig,
                                 Trainer)


def build_parser() -> argparse.ArgumentParser:
    """The launcher CLI; --attack accepts every registered repro.attacks
    name plus the historical aliases (resolved by the registry)."""
    ap = add_common_flags(argparse.ArgumentParser())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--machines", type=int, default=4)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "qn"],
                    help="adamw: robust-aggregated data parallel; "
                    "qn: the paper's five-transmission quasi-Newton "
                    "protocol as the train step")
    ap.add_argument("--agg", default="dcq",
                    choices=sorted(registered_aggregators()),
                    help="robust aggregator (repro.agg registry); \"dcq\" "
                    "means the MAD-self-calibrated \"dcq_mad\" on both "
                    "paths — the training wire carries no variance "
                    "estimates")
    ap.add_argument("--dp-sigma", type=float, default=0.0)
    ap.add_argument("--eps", type=float, default=0.0,
                    help="per-step DP budget; > 0 turns on per-leaf "
                    "calibrated noise (eps/5 per transmission on the qn "
                    "path, mean-mechanism sigma on the adamw path)")
    ap.add_argument("--byzantine", type=float, default=0.0)
    ap.add_argument("--attack", default="scale",
                    choices=sorted(set(registered_attacks())
                                   | set(ATTACK_ALIASES)))
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--hist", type=int, default=5,
                    help="L-BFGS memory length (qn path)")
    ap.add_argument("--ckpt", default="")
    return ap


def main(argv=None):
    """Run the launcher; returns ``(trainer, losses)``."""
    args = build_parser().parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    model = Model(cfg, remat=True)
    params = model.init(stream_key(args.seed, "params"))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[train] {cfg.name} ({'reduced' if args.reduced else 'full'}): "
          f"{n_params/1e6:.1f}M params, {args.machines} machines, "
          f"opt={args.optimizer} agg={args.agg} sigma={args.dp_sigma} "
          f"eps={args.eps} byz={args.byzantine}")

    mesh = None
    if args.sharded:
        mesh = machine_mesh(args.machines)
        print(f"[train] machine axis sharded over "
              f"{jax.device_count()} device(s)")

    attack = args.attack if args.byzantine > 0 else "none"
    if args.optimizer == "qn":
        # the qn wire transmits no variance estimates, so oracle-scale
        # "dcq" maps to its MAD-self-calibrated variant (grad_agg does
        # the same mapping on the adamw path)
        agg = "dcq_mad" if args.agg == "dcq" else args.agg
        qcfg = QNTrainConfig(
            n_machines=args.machines, attack=attack,
            protocol=TreeProtocolConfig(hist=args.hist, lr=args.lr,
                                        eps=args.eps, aggregator=agg,
                                        accountant=args.accountant))
        trainer = QNTrainer(model, qcfg, mesh=mesh)
    else:
        tcfg = TrainConfig(
            n_machines=args.machines, remat=True,
            agg=GradAggConfig(method=args.agg, dp_sigma=args.dp_sigma,
                              attack=attack, dp_eps=args.eps,
                              dp_n=args.batch // args.machines))
        opt = AdamW(lr=args.lr)
        trainer = Trainer(model, opt, tcfg, mesh=mesh)

    n_byz = int(args.byzantine * args.machines)
    byz_mask = (jnp.arange(args.machines) < n_byz) if n_byz else None
    batches = synthetic_lm_batches(stream_key(args.seed, "batches"), cfg,
                                   args.steps, args.batch, args.seq)

    t0 = time.time()
    losses = []

    def cb(i, metrics):
        losses.append(float(metrics["loss"]))
        if i % 10 == 0:
            print(f"  step {i:4d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({time.time()-t0:.1f}s)")

    params, opt_state, _ = trainer.fit(params, batches,
                                       stream_key(args.seed, "protocol"),
                                       byz_mask=byz_mask, callback=cb)
    print(f"[train] done: first loss {losses[0]:.4f} -> last "
          f"{losses[-1]:.4f} in {time.time()-t0:.1f}s")
    if args.ckpt:
        checkpoint.save(args.ckpt, params, opt_state, step=args.steps,
                        meta={"arch": args.arch, "agg": args.agg,
                              "optimizer": args.optimizer})
        print(f"[train] checkpoint -> {args.ckpt}")
    return trainer, losses


if __name__ == "__main__":
    enable_compile_cache()
    main()
