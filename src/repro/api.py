"""``repro.api`` — the stable public surface of this repository.

One import gives the four things a user of the reproduction actually
does, decoupled from the internal package layout (which this facade is
free to keep stable across refactors — tests/test_api.py snapshots the
surface and CI fails on any break):

  * :func:`run_protocol`     — one replicate of the paper's Algorithm 1
    (DP quasi-Newton robust estimation) over pre-sharded data;
  * :func:`run_monte_carlo`  — the batched replicate driver (one compiled
    vmap over PRNG keys);
  * :func:`run_sweep`        — the scenario-sweep engine over the paper's
    experiment grid, by preset name or explicit scenario list;
  * :func:`serve`            — the streaming aggregation service
    (continuous batching over a fixed-capacity ring buffer).

plus the registry views (:func:`registered_aggregators`,
:func:`registered_attacks`) and the config/result types those entry
points consume.

:func:`run_protocol` and :func:`run_monte_carlo` keep the
:class:`DPQNProtocol` they build, one per equal (problem, cfg), the
least recently used of at most 32 going first: a later call with an
equal problem and config reuses its compiled engines, with no trace and
no lowering (:func:`run_monte_carlo` says what such a call does not see
again). Internal modules (``repro.core.*``, ``repro.agg.*``,
``repro.sweep.*``) remain importable but are NOT covered by the
stability promise; the deprecated PR1-era shims (``core/robust_agg``,
``core/dcq``, ``core/byzantine``, ``kernels/dcq*``) warn and will be
removed.
"""
from __future__ import annotations

import collections
import copy
import threading
from typing import Any, Optional

import jax

from repro import agg as _agg
from repro import attacks as _attacks
from repro.agg import dispatch as _dispatch
from repro.agg import registry as _agg_registry
from repro.attacks import registry as _attack_registry
from repro.configs.base import ProtocolConfig
from repro.core.losses import MEstimationProblem, get_problem
from repro.core.protocol import DPQNProtocol, ProtocolResult
from repro.privacy import registry as _privacy_registry
from repro.serve import AggregationService, FlushPolicy, RingBuffer, \
    ServeConfig

__all__ = [
    "run_protocol", "run_monte_carlo", "run_sweep", "serve",
    "registered_aggregators", "registered_attacks",
    "ProtocolConfig", "ProtocolResult", "DPQNProtocol",
    "MEstimationProblem", "get_problem",
    "AggregationService", "ServeConfig", "FlushPolicy", "RingBuffer",
]


#: how many (problem, cfg) protocols the facade keeps
_PROTOCOLS_KEPT = 32
_protocols: "collections.OrderedDict[tuple, DPQNProtocol]" = \
    collections.OrderedDict()
_protocols_lock = threading.Lock()


def _problem_key(prob: MEstimationProblem) -> tuple:
    """The problem by value: its type, and its attributes' names, types
    and values."""
    return (type(prob),) + tuple(sorted(
        (name, type(v), v) for name, v in vars(prob).items()))


def _protocol(problem: Any, cfg: Optional[ProtocolConfig]) -> DPQNProtocol:
    """The kept :class:`DPQNProtocol` of an equal (problem, cfg), built
    on a miss; a new, unkept one where the problem's attributes or the
    config do not hash. The key also holds the generations of the
    module state the engine's trace reads through public calls: the
    aggregator, attack and accountant registries and the backend
    dispatch tables."""
    prob = get_problem(problem) if isinstance(problem, str) else problem
    cfg = cfg if cfg is not None else ProtocolConfig()
    try:
        key = (_problem_key(prob), cfg, _agg_registry.generation(),
               _attack_registry.generation(),
               _privacy_registry.generation(), _dispatch.generation())
        hash(key)
    except TypeError:
        return DPQNProtocol(prob, cfg)
    with _protocols_lock:
        proto = _protocols.get(key)
        if proto is None:
            # a copy, so a later change to the caller's instance cannot
            # reach the engines kept under its old values
            proto = _protocols[key] = DPQNProtocol(copy.copy(prob), cfg)
            if len(_protocols) > _PROTOCOLS_KEPT:
                _protocols.popitem(last=False)
        else:
            _protocols.move_to_end(key)
    return proto


def run_protocol(X, y, problem: Any = "logistic",
                 cfg: Optional[ProtocolConfig] = None,
                 key: Optional[jax.Array] = None, seed: int = 0,
                 **kwargs) -> ProtocolResult:
    """One replicate of Algorithm 1 over pre-sharded data.

    ``X``: (m+1, n, p), ``y``: (m+1, n) — machine 0 is the central
    processor. ``problem`` is a registered loss name or an
    :class:`MEstimationProblem`; ``cfg`` defaults to the paper's
    :class:`ProtocolConfig`. Extra keyword arguments (``byz_mask``,
    ``attack``, ``attack_factor``, ``theta0``, ...) forward to
    :meth:`DPQNProtocol.run`. The engine is reused across calls with an
    equal (problem, cfg), as :func:`run_monte_carlo` says.
    """
    if key is None:
        key = jax.random.PRNGKey(seed)
    return _protocol(problem, cfg).run(key, X, y, **kwargs)


def run_monte_carlo(X, y, reps: int = 100, problem: Any = "logistic",
                    cfg: Optional[ProtocolConfig] = None,
                    keys: Optional[jax.Array] = None, seed: int = 0,
                    **kwargs):
    """Batched Monte-Carlo replicates of Algorithm 1: one compiled vmap
    over the replicate keys. Returns a ``ProtocolArrays`` whose every
    field has a leading replicate axis (``theta_qn``: (reps, p)).

    The :class:`DPQNProtocol` built for ``(problem, cfg)`` is kept and
    reused by every later call with an equal pair, so its compiled
    engine serves them with no trace and no lowering. Equal means: the
    same registered name or problem type with equal attribute values
    (``HuberRegression(c=1.0)`` and ``HuberRegression(c=2.0)`` differ,
    and changing an instance's attribute builds anew), and an equal
    ``ProtocolConfig``. A problem whose attributes, or a config whose
    fields, do not hash is built anew on every call. JAX's own
    configuration, such as ``jax.default_matmul_precision``, is part of
    the compiled engine's key, and so are shapes and the attack mode.
    Registering an aggregator, attack or accountant, and
    ``repro.agg.dispatch.set_table``/``clear_cache``, make the next
    call build anew. A reused engine does not see again, as a
    :class:`DPQNProtocol` held by the caller does not: a change inside
    an attribute value that hashes by identity, a changed class
    attribute or method of the problem's type, or any other module
    state the trace reads.
    """
    if keys is None:
        keys = jax.random.split(jax.random.PRNGKey(seed), reps)
    return _protocol(problem, cfg).run_monte_carlo(keys, X, y, **kwargs)


def run_sweep(scenarios: Any = "smoke", fast: bool = False,
              artifact_path: Optional[str] = None, **kwargs) -> dict:
    """Run a scenario sweep and return its artifact dict.

    ``scenarios`` is a preset name (see ``repro.sweep.PRESETS``) or an
    iterable of ``Scenario`` objects; ``fast=True`` runs the reduced-
    replicate CI variant of a preset. Extra keyword arguments (``mesh``,
    ``resume``, ``chunk_size``, ...) forward to
    ``repro.sweep.run_scenarios``.
    """
    from repro import sweep as _sweep   # heavy import kept lazy
    if isinstance(scenarios, str):
        scens = _sweep.build_preset(scenarios)
    else:
        scens = list(scenarios)
    if fast:
        scens = _sweep.fast_variant(scens)
    return _sweep.run_scenarios(scens, artifact_path=artifact_path,
                                **kwargs)


def serve(theta: Any, cfg: Optional[ServeConfig] = None,
          policy: Optional[FlushPolicy] = None,
          sharding: Optional[Any] = None,
          **cfg_kwargs) -> AggregationService:
    """Stand up a streaming aggregation service around a model.

    ``theta`` is the served model (flat parameter vector or pytree).
    Pass a full :class:`ServeConfig`, or its fields directly as keyword
    arguments (``serve(theta, method="median", capacity=4096, eps=1.0)``).
    Returns a live :class:`AggregationService`; feed it with
    ``submit``/``submit_many``, tick ``poll`` for deadline flushes.
    """
    if cfg is not None and cfg_kwargs:
        raise ValueError("pass either cfg or ServeConfig fields, not both")
    if cfg is None:
        cfg = ServeConfig(**cfg_kwargs)
    return AggregationService(theta, cfg, policy=policy, sharding=sharding)


def registered_aggregators() -> tuple:
    """Names of every registered robust-aggregation rule."""
    return _agg.registered()


def registered_attacks() -> tuple:
    """Names of every registered Byzantine attack."""
    return _attacks.registered()
