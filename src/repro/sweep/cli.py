"""``python -m repro.sweep`` — run a scenario sweep preset end-to-end.

Examples::

    python -m repro.sweep --preset smoke
    python -m repro.sweep --preset paper --out experiments/paper.json
    python -m repro.sweep --preset fig-eps --list     # show grid, don't run

The artifact (versioned JSON, see repro/sweep/artifact.py) is written
after every jit group; re-running the same command resumes from the
completed scenarios unless ``--no-resume``. ``--csv`` additionally emits a
flat per-scenario table.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.sweep import artifact as artifact_mod
from repro.sweep.executor import SweepExecutor
from repro.sweep.grid import group_label, group_scenarios
from repro.sweep.presets import PRESETS, build_preset, fast_variant


def _default_out(preset: str) -> str:
    return f"experiments/sweep_{preset}.json"


def _summarize(art) -> str:
    lines = []
    header = (f"{'scenario':<58} {'metric':>10} {'value':>9}")
    lines.append(header)
    lines.append("-" * len(header))
    for sid, rec in art["scenarios"].items():
        for name, val in sorted(rec["metrics"].items()):
            if isinstance(val, (int, float)):   # skip e.g. loss curves
                lines.append(f"{sid:<58} {name:>10} {val:9.4f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.sweep",
        description="Scenario-sweep engine over the paper's §5 grid "
                    "(losses x attacks x aggregators x eps x m x alpha).")
    ap.add_argument("--preset", default="smoke", choices=sorted(PRESETS),
                    help="scenario grid to run (default: smoke)")
    ap.add_argument("--out", default=None,
                    help="artifact path (default: experiments/"
                         "sweep_<preset>.json)")
    ap.add_argument("--csv", default=None,
                    help="also write a flat CSV of per-scenario rows")
    ap.add_argument("--fast", action="store_true",
                    help="reduced replicate counts (CI smoke of big grids)")
    ap.add_argument("--no-resume", action="store_true",
                    help="ignore any partial artifact at --out")
    ap.add_argument("--no-thetas", action="store_true",
                    help="do not store per-replicate theta_qn in the "
                         "artifact")
    ap.add_argument("--list", action="store_true",
                    help="print the expanded grid and jit groups, then exit")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the machine axis over all visible devices "
                         "(dist/sharded_protocol machine map)")
    ap.add_argument("--max-batch", type=int, default=None, metavar="N",
                    help="chunk jit groups larger than N scenarios into "
                         "bounded batches (caps peak memory; the artifact "
                         "is written after every chunk)")
    from repro.privacy import registered as registered_accountants
    ap.add_argument("--accountant", default=None,
                    choices=registered_accountants(),
                    help="override every scenario's privacy accountant "
                         "(repro.privacy registry) — the nightly "
                         "accountant-sweep runs one preset per entry")
    args = ap.parse_args(argv)

    scenarios = build_preset(args.preset)
    if args.fast:
        scenarios = fast_variant(scenarios)
    if args.accountant is not None:
        import dataclasses
        scenarios = [dataclasses.replace(s, accountant=args.accountant)
                     for s in scenarios]
    groups = group_scenarios(scenarios)
    print(f"preset {args.preset!r}: {len(scenarios)} scenarios in "
          f"{len(groups)} jit group(s)")
    if args.list:
        for key, scens in groups.items():
            print(f"  {group_label(key)}  [{len(scens)} scenario(s)]")
            for s in scens:
                print(f"    {s.scenario_id()}")
        return 0

    mesh = None
    if args.sharded:
        import jax
        from repro.launch.mesh import make_machine_mesh
        n_dev = jax.device_count()
        mesh = make_machine_mesh(n_dev)
        print(f"sharding machine axis over {n_dev} device(s)")

    out = args.out or _default_out(args.preset)
    executor = SweepExecutor(mesh=mesh, progress=print,
                             chunk_size=args.max_batch)
    t0 = time.time()
    art = executor.run(scenarios, artifact_path=out,
                       resume=not args.no_resume,
                       store_thetas=not args.no_thetas,
                       meta={"preset": args.preset, "fast": args.fast})
    dt = time.time() - t0
    print(_summarize(art))
    print(f"\n{len(art['scenarios'])} scenario(s) in artifact; "
          f"this run: {dt:.1f}s, "
          f"{sum(c for c in executor.trace_counts.values())} trace(s) over "
          f"{len(executor.trace_counts)} jit group(s)")
    print(f"wrote {out}")
    if args.csv:
        artifact_mod.to_csv(art, args.csv)
        print(f"wrote {args.csv}")
    # compile-once contract: a group that traced more than once is a bug
    over = {k: c for k, c in executor.trace_counts.items() if c > 1}
    if over:
        print(f"WARNING: {len(over)} jit group(s) retraced: "
              f"{[group_label(k) for k in over]}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch.cli import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
