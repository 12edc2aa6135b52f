"""The command itself: it refuses a host without a TPU, naming the platform
it found, and it refuses a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "fleetopt-qwen3-235b-a22b-h100.azure-10k"
ARGS = ["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_refuses_the_cpu():
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=ROOT,
                       env=_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr


def test_refuses_an_unknown_cell():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "no-such.cell", *ARGS[2:]], cwd=ROOT, env=_env(),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no workload" in p.stderr


def test_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".xla_cache",
                                                  "__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS],
                       cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
