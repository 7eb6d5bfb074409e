"""Algorithm 1 executed SPMD: one machine's shard per device.

The sequential reference (core/protocol.py) expresses every machine-local
computation as ``machine_map(fn, *machine_args, bcast=...)`` with
``jax.vmap`` as the default map. Here the same protocol runs with a
shard_map-based machine map over a 1-D ``("machines",)`` mesh:

  * ``X``/``y`` are placed with the machine axis sharded — each device
    holds exactly its machines' raw data, which never moves;
  * the five per-machine statistics rounds (local M-estimator, gradients,
    Newton directions, gradient differences, BFGS directions) run in
    parallel, one shard per device, with round-level broadcast inputs
    (theta_cq, g_cq, ...) replicated;
  * the central quasi-Newton update — aggregation, DP accounting, the
    rank-1 BFGS correction — is *the same code* as the reference, applied
    to the gathered five-vector transmissions.

Because the per-machine math and the central math are shared with the
sequential implementation, the noiseless protocol matches it to fp32
round-off (<=1e-5 in tests/test_dist.py).

The pure core (core/protocol.py protocol_rounds) is machine-map-agnostic,
so the whole SPMD protocol jit-compiles once per (mesh, shape) through the
same compile-once engine as the single-host path — shard_map composes with
jax.jit — and repeated run_sharded calls on one protocol instance reuse
the compiled executable.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ProtocolConfig, TreeProtocolConfig
from repro.core.losses import MEstimationProblem
from repro.core.protocol import (DPQNProtocol, ProtocolResult,
                                 ProtocolTreeArrays, protocol_tree_rounds)


def machine_map(mesh: Mesh, axis: str = "machines"):
    """Build a mesh-sharded drop-in for core.protocol.vmap_machines.

    ``machine_args`` arrive with the machine axis leading and sharded over
    ``axis``; ``bcast`` values are replicated to every device. Inside the
    shard each device vmaps over its local machines (usually exactly one),
    so per-machine numerics are identical to the sequential reference.
    """
    def mmap(fn, *machine_args, bcast=()):
        n_machine = len(machine_args)

        def per_shard(*args):
            local_args, bc = args[:n_machine], args[n_machine:]
            return jax.vmap(lambda *ma: fn(*ma, *bc))(*local_args)

        in_specs = (P(axis),) * n_machine + (P(),) * len(bcast)
        return jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=P(axis), check_vma=False)(
                             *machine_args, *bcast)
    return mmap


def center_map(mesh: Mesh):
    """Build the center placement for ``mesh`` (core.protocol.at_center's
    mesh form). The center receives every machine's transmission and
    aggregates it; here each device plays the center on the gathered,
    replicated rows, so the result is replicated too. It is also what lets
    the Pallas order-statistics kernel run on a mesh: a Mosaic kernel
    cannot be partitioned automatically, but inside ``shard_map`` each
    device runs it whole."""
    def center(fn, *args):
        return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(*args)
    return center


def run_sharded(prob: MEstimationProblem, cfg: ProtocolConfig, mesh: Mesh,
                key: jax.Array, X: jnp.ndarray, y: jnp.ndarray,
                byz_mask: Optional[jnp.ndarray] = None,
                attack: str = "scale", attack_factor: float = -3.0,
                theta0: Optional[jnp.ndarray] = None,
                jit: bool = True) -> Dict[str, object]:
    """Run Algorithm 1 with machines sharded over ``mesh``'s first axis.

    ``X``: (m+1, n, p), ``y``: (m+1, n) — machine 0 is the central
    processor, as in ``DPQNProtocol.run``; m+1 must divide evenly over the
    mesh axis. Returns the three estimators plus the full ProtocolResult.
    """
    axis = mesh.axis_names[0]
    n_dev = mesh.shape[axis]
    if X.shape[0] % n_dev:
        raise ValueError(
            f"{X.shape[0]} machines do not shard evenly over "
            f"{n_dev} devices on axis {axis!r}")
    machine_sharding = NamedSharding(mesh, P(axis))
    X = jax.device_put(X, machine_sharding)
    y = jax.device_put(y, machine_sharding)
    proto = DPQNProtocol(prob, cfg, machine_map=machine_map(mesh, axis),
                         jit=jit, center=center_map(mesh))
    res: ProtocolResult = proto.run(key, X, y, byz_mask=byz_mask,
                                    attack=attack,
                                    attack_factor=attack_factor,
                                    theta0=theta0)
    return {"theta_cq": res.theta_cq, "theta_os": res.theta_os,
            "theta_qn": res.theta_qn, "result": res}


def run_sharded_tree(key: jax.Array, theta, batches, grad_fn,
                     cfg: TreeProtocolConfig, mesh: Mesh, mem=None,
                     byz_mask: Optional[jnp.ndarray] = None,
                     attack: str = "none", attack_factor: float = -3.0,
                     n: Optional[int] = None,
                     jit: bool = True) -> ProtocolTreeArrays:
    """The pytree protocol with machines sharded over ``mesh``'s first
    axis: each device holds its machines' batch shard (raw data never
    moves), the five per-machine statistics rounds run one shard per
    device through the same ``machine_map``, and every leaf of every
    transmission is aggregated by the same central code as the
    single-host engine. ``shard_map``'s spec prefixes broadcast
    ``P(axis)`` over pytree machine args, so parameter trees and the
    per-machine L-BFGS memory shard without per-leaf plumbing.

    ``batches``: pytree with leading machine axis m (must divide the mesh
    axis evenly). The other arguments are ``protocol_tree_rounds``'s.
    """
    axis = mesh.axis_names[0]
    n_dev = mesh.shape[axis]
    m = jax.tree_util.tree_leaves(batches)[0].shape[0]
    if m % n_dev:
        raise ValueError(
            f"{m} machines do not shard evenly over {n_dev} devices on "
            f"axis {axis!r}")
    machine_sharding = NamedSharding(mesh, P(axis))
    batches = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, machine_sharding), batches)
    mmap = machine_map(mesh, axis)

    def run(key, theta, batches, mem, byz_mask):
        return protocol_tree_rounds(
            key, theta, batches, grad_fn, cfg, mem=mem, byz_mask=byz_mask,
            attack=attack, attack_factor=attack_factor, n=n,
            machine_map=mmap, center=center_map(mesh))
    if jit:
        run = jax.jit(run)
    return run(key, theta, batches, mem, byz_mask)
