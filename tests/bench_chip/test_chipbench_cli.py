"""``run.py`` refuses to measure anywhere but on a TPU, and prints no
result when it does."""
import os
import shutil
import subprocess
import sys

from benchmarks.chip import harness


def _run(cwd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "googlenet.b8", "--seed", "2147483999", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_exits_nonzero_without_a_chip_and_prints_no_metric():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_from_the_benchmark_files_alone(tmp_path):
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
