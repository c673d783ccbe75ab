"""A measuring run fails, and prints no result, without a TPU."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi2.hw_plane",
         "--seed", "4294967301", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = run_bench(ROOT)
    assert p.returncode == 3, p.stderr
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
