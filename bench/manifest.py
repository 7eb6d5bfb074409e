"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (``workloads`` entry) names a configuration and a traffic mix; the
configuration is ``bench/configs/<config>.json``, the traffic mix
``bench/traffic/<workload>.json`` (which names the entry that drives
it), and each per-layer metric ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, List

ROOT = Path(__file__).resolve().parent.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def load(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise ManifestError(f"no {path}") from None


def config_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "configs" / f"{name}.json"


def traffic_path(workload: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{workload}.json"


def metric_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "metrics" / f"{name}.py"


def entry_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "entries" / f"{name}.py"


def workload(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json; cells: "
                        f"{[w['name'] for w in manifest['workloads']]}")


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise ManifestError(f"missing {path}") from None


def resolve(manifest: dict, name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs: its entry in ``workloads``, its
    configuration, its traffic mix and the metrics it reports."""
    cell = workload(manifest, name)
    config = _read_json(config_path(cell["config"], root))
    traffic = _read_json(traffic_path(name, root))
    if traffic.get("config") != cell["config"]:
        raise ManifestError(f"bench/traffic/{name}.json names config "
                            f"{traffic.get('config')!r}, the cell "
                            f"{cell['config']!r}")
    if not entry_path(traffic["entry"], root).is_file():
        raise ManifestError(f"traffic {name} names entry "
                            f"{traffic['entry']!r}: no such file")
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": metrics_of(manifest, "end_to_end", name),
            "per_layer": metrics_of(manifest, "per_layer", name)}


def metrics_of(manifest: dict, kind: str, workload_name: str) -> List[dict]:
    """The ``kind`` ("end_to_end" or "per_layer") metrics a cell reports:
    those whose ``workloads`` list it, and those without the key."""
    return [m for m in manifest[kind]
            if workload_name in m.get("workloads", [workload_name])]


def problems(manifest: dict, root: Path = ROOT) -> List[str]:
    """What is wrong with the manifest and the files it names (empty when
    every name resolves and every rule the harness relies on holds)."""
    out: List[str] = []
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names: Dict[str, str] = {}
    group = {"configs": "config", "workloads": "cell",
             "end_to_end": "metric", "per_layer": "metric"}
    for kind in group:
        for item in manifest[kind]:
            n = item["name"]
            if not NAME_RE.match(n):
                out.append(f"{kind} name {n!r} has a disallowed character")
            if names.get(n) == group[kind]:
                out.append(f"{group[kind]} name {n!r} appears twice")
            names[n] = group[kind]
            if "unit" in item and not UNIT_RE.match(item["unit"]):
                out.append(f"unit {item['unit']!r} of {n} is not allowed")
    for c in manifest["configs"]:
        path = config_path(c["name"], root)
        if not path.is_file():
            out.append(f"config {c['name']}: no {path}")
        elif c["file"] != str(path.relative_to(root)):
            out.append(f"config {c['name']}: file {c['file']} is not "
                       f"{path.relative_to(root)}")
        for k in c["reduced"]:
            if not NAME_RE.match(k):
                out.append(f"reduced key {k!r} has a disallowed character")
        if not any(w["config"] == c["name"] for w in cells.values()):
            out.append(f"config {c['name']} is used by no cell")
    for name, w in cells.items():
        if w["config"] not in configs:
            out.append(f"cell {name}: unknown config {w['config']}")
        if not NAME_RE.match(w["traffic"]):
            out.append(f"cell {name}: traffic name {w['traffic']!r}")
        if name != f"{w['config']}.{w['traffic']}":
            out.append(f"cell {name} is not <config>.<traffic>")
        try:
            resolve(manifest, name, root)
        except ManifestError as e:
            out.append(str(e))
        reported = [m["name"] for m in metrics_of(manifest, "end_to_end",
                                                  name)]
        if "setup_s" not in reported or len(reported) < 2:
            out.append(f"cell {name} reports {reported}: needs setup_s "
                       f"and another end-to-end metric")
        if not metrics_of(manifest, "per_layer", name):
            out.append(f"cell {name} reports no per-layer metric")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for w in m.get("workloads", []):
            if w not in cells:
                out.append(f"metric {m['name']} lists unknown cell {w}")
    for m in manifest["per_layer"]:
        if not metric_path(m["name"], root).is_file():
            out.append(f"per-layer metric {m['name']}: no "
                       f"{metric_path(m['name'], root)}")
        moved = e2e.get(m["moves"])
        if moved is None:
            out.append(f"{m['name']} moves unknown metric {m['moves']}")
            continue
        for w in m.get("workloads", list(cells)):
            if w not in moved.get("workloads", list(cells)):
                out.append(f"{m['name']} is read in {w}, which does not "
                           f"report {m['moves']}")
    return out
