"""``bench/run.py`` refuses to measure anywhere but on the chips a cell
asks for, and prints no result line when it does."""
import json
import os
import shutil
import subprocess
import sys

from bench_testlib import BENCH, REPO


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.loads((REPO / "BENCHMARK.json").read_text())["workloads"][0]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         cell["name"], "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cpu_is_refused_before_measuring():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
