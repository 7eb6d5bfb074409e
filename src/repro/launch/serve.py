"""Serving launcher: the streaming aggregation service over a simulated
fleet.

Stands up :class:`repro.serve.AggregationService` around a model-zoo
parameter pytree and drives it with synthetic fleet traffic — machine
updates stream in (optionally Byzantine-corrupted through the
``repro.attacks`` registry and thinned by a straggler dropout rate), the
device-resident ring buffer absorbs them with compiled donated writes,
and the single compiled masked-aggregation step serves a model update
every time the flush policy fires. Partial fleets (stragglers) flush at
the deadline with the SAME executable — ``fill`` is a traced scalar.

  python -m repro.launch.serve --config xlstm-125m --machines 64 \
      --rounds 5 --agg dcq_mad --eps 1.0 --byzantine 0.2 --attack signflip

``--sharded`` places the ring buffer's capacity axis over all visible
devices (pair with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
on CPU).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.agg import has_masked
from repro.agg import registered as registered_aggregators
from repro.attacks import ALIASES as ATTACK_ALIASES
from repro.attacks import registered as registered_attacks
from repro.configs import get_config
from repro.core.keys import stream_key
from repro.core.transport import wire_corrupt
from repro.launch.cli import (add_common_flags, enable_compile_cache,
                              machine_mesh)
from repro.models.model import Model
from repro.serve import AggregationService, FlushPolicy, ServeConfig


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI; mirrors launch/train.py (shared flags come from
    launch/cli.py, --agg/--attack from the registries)."""
    ap = add_common_flags(argparse.ArgumentParser())
    ap.add_argument("--machines", type=int, default=64,
                    help="fleet size per round (ring-buffer capacity)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--agg", default="dcq_mad",
                    choices=sorted(n for n in registered_aggregators()
                                   if has_masked(n)),
                    help="robust aggregator (repro.agg registry, masked "
                    "partial-fill form required for serving)")
    ap.add_argument("--eps", type=float, default=0.0,
                    help="per-round DP budget; > 0 adds per-leaf "
                    "calibrated noise inside the compiled step")
    ap.add_argument("--delta", type=float, default=1e-6)
    ap.add_argument("--byzantine", type=float, default=0.0,
                    help="fraction of the fleet sending corrupted updates")
    ap.add_argument("--attack", default="scale",
                    choices=sorted(set(registered_attacks())
                                   | set(ATTACK_ALIASES)))
    ap.add_argument("--attack-factor", type=float, default=-3.0)
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="straggler fraction: each round this share of "
                    "the fleet never arrives and the round flushes "
                    "partial (same executable, traced fill)")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--ingest-block", type=int, default=64,
                    help="bulk-ingest chunk (one compiled write per chunk)")
    ap.add_argument("--min-fill", type=int, default=1)
    return ap


def fleet_round(key: jax.Array, params, m: int, byz_mask, attack: str,
                factor: float):
    """One round of synthetic fleet traffic: unit-scale machine updates
    around a shared drift, Byzantine rows corrupted on the wire."""
    k_drift, k_noise, k_byz = jax.random.split(key, 3)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    kd = jax.random.split(k_drift, len(leaves))
    kn = jax.random.split(k_noise, len(leaves))
    ups = [jax.random.normal(d, x.shape, x.dtype)
           + 0.3 * jax.random.normal(n, (m,) + x.shape, x.dtype)
           for x, d, n in zip(leaves, kd, kn)]
    updates = jax.tree_util.tree_unflatten(treedef, ups)
    return wire_corrupt(k_byz, updates, byz_mask, attack=attack,
                        factor=factor)


def main(argv=None):
    args = build_parser().parse_args(argv)

    cfg = get_config(args.arch, reduced=True)
    model = Model(cfg)
    params = model.init(stream_key(args.seed, "params"))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

    sharding = None
    if args.sharded:
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = machine_mesh(args.machines)
        sharding = NamedSharding(mesh, PartitionSpec("machines"))
        print(f"[serve] ring buffer sharded over "
              f"{jax.device_count()} device(s)")

    scfg = ServeConfig(method=args.agg, capacity=args.machines,
                       lr=args.lr, eps=args.eps, delta=args.delta,
                       ingest_block=min(args.ingest_block, args.machines),
                       seed=args.seed, accountant=args.accountant)
    policy = FlushPolicy(min_fill=args.min_fill)
    svc = AggregationService(params, scfg, policy=policy,
                             sharding=sharding)
    print(f"[serve] {cfg.name}: {n_params/1e6:.1f}M params, fleet "
          f"m={args.machines}, agg={args.agg} eps={args.eps} "
          f"byz={args.byzantine} dropout={args.dropout}")

    n_byz = int(args.byzantine * args.machines)
    byz_mask = (jnp.arange(args.machines) < n_byz) if n_byz else None
    attack = args.attack if n_byz else "none"

    t0 = time.time()
    for r in range(args.rounds):
        key = stream_key(args.seed, "serve", index=r + 1)
        updates = fleet_round(key, params, args.machines, byz_mask,
                              attack, args.attack_factor)
        arrive = args.machines
        if args.dropout > 0:
            arrive = max(args.min_fill,
                         args.machines - int(args.dropout * args.machines))
            updates = jax.tree_util.tree_map(lambda x: x[:arrive], updates)
        svc.submit_many(updates)
        if svc.fill:             # stragglers: deadline-style partial flush
            svc.flush()
        h = svc.history[-1]
        print(f"  round {h['round']:3d} fill {h['fill']:5d}/"
              f"{args.machines} latency {h['latency_s']*1e3:7.2f} ms")
    dt = time.time() - t0

    served = sum(h["fill"] for h in svc.history)
    steady = [h["flush_s"] for h in svc.history[1:]] or \
        [svc.history[-1]["flush_s"]]
    print(f"[serve] {svc.round_idx} rounds, {served} updates in "
          f"{dt:.2f}s; steady flush {min(steady)*1e3:.2f} ms; "
          f"traces {svc.trace_counts}")
    if args.eps > 0:
        print(svc.accountant.summary())
    return svc


if __name__ == "__main__":
    enable_compile_cache()
    main()
