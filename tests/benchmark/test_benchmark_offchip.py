"""``benchmark/run.py`` off the chip: it exits nonzero and prints no
result line, whether it finds no TPU or no program to run."""

import os
import shutil
import subprocess
import sys

from bench_helpers import BENCH_DIR, ROOT


def _run(cwd, workload="large-cluster.full_sweep"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not tpu" in p.stderr


def test_unknown_cell_no_result():
    p = _run(ROOT, workload="no.such_cell")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_alone_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
