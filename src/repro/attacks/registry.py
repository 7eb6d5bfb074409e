"""Attack registry: one entry per Byzantine wire-corruption rule.

The paper's robustness claims (§1.1, §5.1) are statements about a threat
model: some machines transmit adversarial statistics instead of honest
ones. This registry is the single place those threat models live — the
adversary-side mirror of the ``repro.agg`` aggregator registry. An
:class:`Attack` bundles

  * ``corrupt``     — the pure jittable corruption rule
    ``(values (m, ...), mask (m,), factor, key) -> values``: it returns
    the adversarial replacement for EVERY row; dispatch
    (``repro.attacks.apply_attack``) masks it back onto the Byzantine
    rows with ``jnp.where``, so honest rows are bit-identical by
    construction and rules never need to touch the mask for writing;
  * ``omniscient``  — whether the rule reads honest-machine statistics
    (ALIE perturbs around the honest mean/std, IPM transmits the negated
    honest mean), which it computes from ``(values, mask)``: corruption
    is applied where the full machine axis is visible, so coordinated
    attacks see exactly what a colluding adversary would see;
  * ``needs_key``   — whether the rule draws randomness; dispatch raises
    a clear ``ValueError`` when the key is omitted instead of crashing
    inside ``jax.random`` with an opaque trace error;
  * ``round_aware`` — whether the rule receives the protocol round index
    (``adaptive_scale`` ramps its corruption over Algorithm 1's rounds);
  * ``factor_grid`` — the sensible sweep values for ``factor``, the axis
    the ``attack-sensitivity`` preset expands per attack.

Registering an attack makes it immediately dispatchable from
``apply_attack``, sweepable (``Scenario.attack`` validates against this
registry exactly as ``Scenario.aggregator`` validates against
``repro.agg``) and selectable from the training launcher.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple


@dataclasses.dataclass(frozen=True)
class Attack:
    """One Byzantine corruption rule over the transmitted machine axis.

    ``corrupt(values, mask, factor, key)`` -> replacement rows, same shape
    and dtype as ``values`` (round-aware rules additionally accept a
    ``round_idx`` keyword). The mask argument is read-only context for
    omniscient rules; dispatch performs the actual row selection.
    """
    name: str
    corrupt: Callable
    #: reads honest-machine statistics via (values, mask)
    omniscient: bool = False
    #: draws randomness; apply_attack raises ValueError if key is None
    needs_key: bool = False
    #: receives round_idx (position within Algorithm 1's transmissions)
    round_aware: bool = False
    #: sensible factor sweep values (empty = not in attack-sensitivity)
    factor_grid: Tuple[float, ...] = ()
    doc: str = ""


_REGISTRY: Dict[str, Attack] = {}
_generation = 0

#: launcher-friendly aliases (the historical dist/grad_agg names)
ALIASES: Dict[str, str] = {"sign": "signflip", "noise": "gauss"}


def register(attack: Attack) -> Attack:
    """Register (or replace) an attack under ``attack.name``."""
    global _generation
    if attack.name in ALIASES:
        raise ValueError(f"{attack.name!r} shadows alias for "
                         f"{ALIASES[attack.name]!r}")
    _REGISTRY[attack.name] = attack
    _generation += 1
    return attack


def unregister(name: str) -> None:
    """Remove a registered attack (tests registering temporary entries
    clean up through this instead of the private dict)."""
    global _generation
    _REGISTRY.pop(name, None)
    _generation += 1


def generation() -> int:
    """Changes with every :func:`register` and :func:`unregister`: a
    cache of what was traced from the registry (``repro.api``'s engines)
    keys its entries by it."""
    return _generation


def resolve(name: str) -> str:
    """Canonical registry name for ``name`` (aliases resolved)."""
    return ALIASES.get(name, name)


def get_attack(name: str) -> Attack:
    try:
        return _REGISTRY[resolve(name)]
    except KeyError:
        raise KeyError(f"unknown attack {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def registered() -> Tuple[str, ...]:
    """Names of all registered attacks, sorted."""
    return tuple(sorted(_REGISTRY))


def needs_key(name: str) -> bool:
    return get_attack(name).needs_key
