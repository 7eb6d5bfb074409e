"""``python3 -m bench`` prints no result and exits non-zero without a
TPU, and in a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from bench import manifest

ARGS = ["-m", "bench", "--workload", "paper-logistic.mc-figeps",
        "--seed", "3000000001", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(manifest.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_only_the_benchmark_no_result(tmp_path):
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(manifest.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
