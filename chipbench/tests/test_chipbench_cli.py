"""The command refuses to measure without a TPU, and prints no result."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells


@pytest.mark.parametrize("trace", ["0", "1"])
def test_no_tpu_no_result(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cells.BENCH_DIR / "run.py"), "--workload",
         "flexemr-zipf-tail", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", trace],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_needs_the_program(tmp_path):
    """In a directory with only the benchmark's own files, no result."""
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "flexemr-zipf-tail",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
