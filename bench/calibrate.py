"""Readings for setting a cell's limits: the program, the control and
the planted faults, seed by seed, with no measured window.

    python3 -m bench.calibrate --workload <name> --seeds 1 2 3 \
        --variants program control [--out readings.jsonl]

Each line of output is one JSON object ``{"seed", "variant",
"readings"}``; the entry's ``calibrate(run, variant, cache)`` computes
them (``cache`` lives as long as the process).
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import manifest
from bench.run import ROOT, Run, _enable_cache, load_module


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["program", "control"])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    _enable_cache()
    spec = manifest.resolve(manifest.load(ROOT), args.workload)
    entry = load_module(manifest.entry_path(spec["traffic"]["entry"]),
                        "bench_entry")
    out = open(args.out, "a") if args.out else None
    cache: dict = {}
    try:
        for seed in args.seeds:
            for variant in args.variants:
                t0 = time.perf_counter()
                r = Run(spec, seed, 0.0, False, t0)
                rd = entry.calibrate(r, variant, cache)
                line = json.dumps({"seed": seed, "variant": variant,
                                   "readings": rd,
                                   "s": time.perf_counter() - t0})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
