"""BENCHMARK.json against the files it names, and a cell, traffic mix
and per-layer metric added by files and entries alone."""
import json
import shutil

import pytest

from bench import manifest


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def test_every_name_resolves_and_every_rule_holds(bench):
    assert manifest.problems(bench) == []


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_names_and_units_use_allowed_characters(bench, kind):
    for m in bench[kind]:
        assert manifest.NAME_RE.match(m["name"]), m["name"]
        assert manifest.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


def test_moves_is_reported_by_every_cell_that_reads_it(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in moved.get("workloads", cells), (m["name"], w)


def test_each_cell_resolves_its_files(bench):
    for w in bench["workloads"]:
        spec = manifest.resolve(bench, w["name"])
        assert spec["traffic"]["config"] == w["config"]
        assert {"setup_s"} < {m["name"] for m in spec["end_to_end"]}
        assert spec["per_layer"]


def test_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
    chips = [w["chips"] for w in bench["workloads"]]
    assert set(chips) <= {1, 4}


def test_a_cell_is_added_by_files_and_entries_only(tmp_path, bench):
    shutil.copytree(manifest.ROOT / "bench", tmp_path / "bench")
    b = json.loads(json.dumps(bench))
    traffic = json.loads(manifest.traffic_path(
        "paper-logistic.mc-figeps").read_text())
    traffic["eps"] = 30.0
    (tmp_path / "bench" / "traffic" / "paper-logistic.mc-eps30.json"
     ).write_text(json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "calls.mc.py").write_text(
        "def read(run, red):\n    return run.values.get('calls')\n")
    b["workloads"].append({"name": "paper-logistic.mc-eps30",
                           "config": "paper-logistic", "traffic": "mc-eps30",
                           "chips": 1, "why": "a later cell"})
    b["end_to_end"][0]["workloads"].append("paper-logistic.mc-eps30")
    b["per_layer"].append({"name": "calls.mc", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "Algorithm 1 engine",
                           "moves": "fits_per_s",
                           "workloads": ["paper-logistic.mc-eps30"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    assert manifest.problems(b, tmp_path) == []
    spec = manifest.resolve(b, "paper-logistic.mc-eps30", tmp_path)
    assert spec["traffic"]["eps"] == 30.0
    assert "calls.mc" in [m["name"] for m in spec["per_layer"]]


def test_a_missing_file_is_a_problem(tmp_path, bench):
    shutil.copytree(manifest.ROOT / "bench", tmp_path / "bench")
    (tmp_path / "bench" / "metrics" / "device_idle.mc.py").unlink()
    assert any("device_idle.mc" in p
               for p in manifest.problems(bench, tmp_path))
