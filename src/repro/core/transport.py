"""Pytree wire transport: the protocol's noise / corrupt / aggregate
primitives over arbitrary gradient pytrees.

Algorithm 1's wire model is: a per-machine statistic is stacked along a
leading machine axis, DP noise is added per machine, Byzantine corruption
replaces the selected rows, and a robust aggregator reduces the machine
axis. At p=10 the statistic is one flat vector; at model scale it is a
parameter pytree. This module is the single implementation of that wire
for both regimes:

  * every primitive takes ``values`` as EITHER a single ``(m, ...)`` array
    OR a pytree of them, and dispatches per leaf;
  * each leaf ``(m, *payload)`` is aggregated whole; repro.agg lays it
    out as ``(rows, m, cols)`` for the batched Pallas order-statistics
    kernel, so that path applies unchanged to every leaf of a model;
  * noise scales (``sigma``) and aggregation scales may be scalars,
    per-machine ``(m,)`` vectors, or pytrees matching ``values`` — the
    per-leaf DP calibration (core/dp.py) feeds pytree sigmas so each
    leaf's Gaussian mechanism uses a sensitivity computed from ITS OWN
    dimension;
  * corruption routes through the ``repro.attacks`` registry per leaf,
    with the transmission index forwarded to round-aware attacks.

Byte-parity invariant (tested in tests/test_protocol_pytree.py): a
SINGLE-leaf tree consumes the transmission PRNG key directly — no
``jax.random.split`` — so the flat ``(m, p)`` protocol refactored onto
these primitives reproduces its pre-refactor draws bit-for-bit, per key.
Multi-leaf trees split the key once per leaf (machines never share leaf
randomness).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import agg, attacks

__all__ = ["tree_leaf_dims", "tree_size", "leaf_paths", "is_single_leaf",
           "wire_noise", "wire_corrupt", "wire_aggregate", "tree_axpy",
           "tree_sub", "tree_add", "tree_scale", "tree_dot"]


# ------------------------------------------------------------ tree algebra

def tree_dot(a: Any, b: Any) -> jnp.ndarray:
    """Global inner product <a, b> over matching pytrees."""
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return sum(jnp.vdot(x, y) for x, y in zip(la, lb))


def tree_add(a: Any, b: Any) -> Any:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a: Any, b: Any) -> Any:
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(c, a: Any) -> Any:
    return jax.tree_util.tree_map(lambda x: c * x, a)


def tree_axpy(c, x: Any, y: Any) -> Any:
    """y + c * x, leaf-wise."""
    return jax.tree_util.tree_map(lambda xx, yy: yy + c * xx, x, y)


# ------------------------------------------------------------- leaf layout

def tree_leaf_dims(tree: Any, machine_axis: bool = False) -> Any:
    """Per-leaf flat dimension d_leaf (ints, same tree structure).

    With ``machine_axis=True`` the leading axis is the machine stack and
    is excluded — d_leaf is the dimension of ONE machine's transmission.
    """
    def dim(leaf):
        shape = tuple(leaf.shape)[1:] if machine_axis else tuple(leaf.shape)
        return int(math.prod(shape)) if shape else 1
    return jax.tree_util.tree_map(dim, tree)


def tree_size(tree: Any, machine_axis: bool = False) -> int:
    """Total transmitted dimension: sum of per-leaf dims."""
    return sum(jax.tree_util.tree_leaves(
        tree_leaf_dims(tree, machine_axis=machine_axis)))


def leaf_paths(tree: Any) -> list:
    """Stable human-readable leaf names ("layers/w_q", ...) in
    tree_leaves order — the per-leaf spend-ledger keys."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, _leaf in flat:
        out.append("/".join(str(getattr(k, "key", getattr(k, "idx", "")))
                            for k in kp) or "theta")
    return out


def is_single_leaf(tree: Any) -> bool:
    return len(jax.tree_util.tree_leaves(tree)) == 1


def _leaf_keys(key: jax.Array, n: int):
    """One PRNG key per leaf. Single-leaf trees consume ``key`` directly:
    this is the byte-parity rule that makes the flat (m, p) protocol a
    strict special case of the pytree wire."""
    return [key] if n == 1 else list(jax.random.split(key, n))


def _match(tree: Any, value: Any) -> list:
    """Broadcast ``value`` (scalar / per-machine vector / matching pytree)
    to one entry per leaf of ``tree``, in tree_leaves order."""
    n = len(jax.tree_util.tree_leaves(tree))
    if jax.tree_util.tree_structure(value, is_leaf=lambda x: x is None) \
            == jax.tree_util.tree_structure(tree):
        return jax.tree_util.tree_leaves(value)
    return [value] * n


def _bcast_sigma(sig, leaf):
    """Scalar sigma, or a per-machine (m,) sigma vector broadcast over the
    leaf's payload dims."""
    sig = jnp.asarray(sig, leaf.dtype)
    if sig.ndim == 1 and leaf.ndim >= 1 and sig.shape[0] == leaf.shape[0]:
        return sig.reshape((-1,) + (1,) * (leaf.ndim - 1))
    return sig


# ----------------------------------------------------------- the wire ops

def wire_noise(key: jax.Array, values: Any, sigma: Any,
               noiseless: bool = False) -> Any:
    """Gaussian mechanism on the wire: every machine row of every leaf
    gets an independent draw. ``sigma``: scalar, per-machine ``(m,)``
    vector, or a pytree of those matching ``values``."""
    if noiseless:
        return values
    leaves, treedef = jax.tree_util.tree_flatten(values)
    sigs = _match(values, sigma)
    keys = _leaf_keys(key, len(leaves))
    noisy = [leaf + _bcast_sigma(s, leaf)
             * jax.random.normal(k, leaf.shape, leaf.dtype)
             for leaf, s, k in zip(leaves, sigs, keys)]
    return jax.tree_util.tree_unflatten(treedef, noisy)


def wire_corrupt(key: Optional[jax.Array], values: Any,
                 byz_mask: Optional[jnp.ndarray], attack: str = "scale",
                 factor=-3.0, round_idx: int = 0) -> Any:
    """Byzantine corruption of the selected machine rows on every leaf,
    through the ``repro.attacks`` registry (omniscient attacks see each
    leaf's full machine axis; round-aware attacks get ``round_idx``)."""
    if byz_mask is None or attacks.resolve(attack) == "none":
        return values
    leaves, treedef = jax.tree_util.tree_flatten(values)
    keys = _leaf_keys(key, len(leaves)) if key is not None \
        else [None] * len(leaves)
    out = [attacks.apply_attack(leaf, byz_mask, attack=attack,
                                factor=factor, key=k, round_idx=round_idx)
           for leaf, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def wire_aggregate(values: Any, method: str, scale: Any = None,
                   K: int = 10, trim_beta: float = 0.2,
                   backend: Optional[str] = None,
                   fill: Optional[Any] = None) -> Any:
    """Robust aggregation of the leading machine axis, per leaf, through
    the ``repro.agg`` registry.

    Every pytree leaf ``(m, *payload)`` goes to the registry whole and
    comes back as ``payload`` (per coordinate, the same numbers as the
    historical flattened ``(m, d_leaf)`` call). Single arrays pass
    through at their native shape (bit-identical to the historical flat
    path).

    ``fill`` (the serving path): when given, the leading axis is a
    fixed-capacity ring buffer whose first ``fill`` (traced scalar) rows
    are valid, and dispatch routes to ``repro.agg.aggregate_masked`` —
    byte-identical to aggregating the dense unpadded prefix, at one trace
    per capacity. On the masked path ``backend`` selects between the
    masked forms (``"sort"`` / ``"bisect"``); ``None`` consults the
    measured dispatch table (``repro.agg.dispatch``).
    """
    if fill is not None:
        if not isinstance(values, (dict, list, tuple)):
            return agg.aggregate_masked(values, fill, method=method,
                                        scale=scale, K=K,
                                        trim_beta=trim_beta, axis=0,
                                        backend=backend)
        leaves, treedef = jax.tree_util.tree_flatten(values)
        out = [agg.aggregate_masked(leaf, fill, method=method, scale=sc,
                                    K=K, trim_beta=trim_beta, axis=0,
                                    backend=backend)
               for leaf, sc in zip(leaves, _match(values, scale))]
        return jax.tree_util.tree_unflatten(treedef, out)
    if not isinstance(values, (dict, list, tuple)):
        # plain (m, p) array: the historical flat call, verbatim —
        # guarantees the refactored protocol_rounds is byte-identical.
        return agg.aggregate(values, method=method, scale=scale, K=K,
                             trim_beta=trim_beta, axis=0, backend=backend)
    leaves, treedef = jax.tree_util.tree_flatten(values)
    scales = _match(values, scale)
    out = []
    for leaf, sc in zip(leaves, scales):
        # leaves go whole: repro.agg lays a 2-D payload out for the kernel
        # (flattening it here costs the TPU compiler minutes per leaf)
        if sc is not None:
            sc = jnp.broadcast_to(jnp.asarray(sc, leaf.dtype), leaf.shape[1:])
        red = agg.aggregate(leaf, method=method, scale=sc, K=K,
                            trim_beta=trim_beta, axis=0, backend=backend)
        out.append(red.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
