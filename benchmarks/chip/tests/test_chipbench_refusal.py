"""The command refuses to run where it cannot measure the chip."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
CELL = ["--workload", "minitron-4b.prune-0.6", "--seed", "3000000007",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script), *CELL], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_means_no_result():
    p = _run(ROOT, HERE / "run.py")
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directory has no program to measure: exit non-zero, no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    dst = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, dst, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, dst / "run.py")
    assert p.returncode != 0
    assert p.stdout == ""


def test_unknown_workload_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload",
                        "no-such-cell", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 2 and p.stdout == ""
