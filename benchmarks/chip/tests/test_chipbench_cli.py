"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from _chipbench_smoke import BENCH

CHECKOUT = BENCH.parents[1]
ARGS = ["--workload", "minicpm3-4b.profile", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)


def test_refuses_without_a_tpu():
    proc = _run(CHECKOUT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no tpu" in proc.stderr.lower()


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
