"""Shared CLI flags for the launchers (train / serve).

Both launchers address the same model zoo and the same reproducibility
and placement knobs; this module is the single definition of those
flags so ``python -m repro.launch.train --help`` and
``... launch.serve --help`` never drift apart on them.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Optional


def add_common_flags(ap: argparse.ArgumentParser,
                     arch_default: str = "xlstm-125m"
                     ) -> argparse.ArgumentParser:
    """The flags every launcher shares: model selection, root seed,
    device placement."""
    ap.add_argument("--config", "--arch", dest="arch", default=arch_default,
                    help="model-zoo config name (repro.configs.ARCHS)")
    ap.add_argument("--seed", type=int, default=0,
                    help="root seed; per-purpose keys are derived as "
                    "independent fold_in streams (repro.core.keys)")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the machine axis over all visible devices")
    from repro.privacy import registered as registered_accountants
    ap.add_argument("--accountant", default="basic",
                    choices=registered_accountants(),
                    help="repro.privacy accountant splitting the total "
                    "(eps, delta) over the DP transmissions (default: "
                    "basic, the paper's even split)")
    return ap


def checkout_root() -> Optional[Path]:
    """The source checkout this package runs from (src layout, as with
    ``pip install -e .`` or ``PYTHONPATH=src``), or None when the package
    is installed elsewhere."""
    root = Path(__file__).resolve().parents[3]
    return root if (root / "pyproject.toml").is_file() else None


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; call it from entry
    points only, never on import. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX already reads it and nothing else is set. Otherwise the cache
    goes to ``.jax_cache`` in the checkout (:func:`checkout_root`); an
    installed package outside a checkout keeps no persistent cache unless
    the variable is set. Returns the directory in use, or None."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    root = checkout_root()
    if root is None:
        return None
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def machine_mesh(n_machines: int):
    """A 1-D device mesh over the machine axis, validating divisibility
    (pair with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on
    CPU)."""
    import jax

    from repro.launch.mesh import make_machine_mesh
    n_dev = jax.device_count()
    if n_machines % n_dev:
        raise SystemExit(f"--machines {n_machines} does not divide over "
                         f"{n_dev} devices")
    return make_machine_mesh(n_dev)
