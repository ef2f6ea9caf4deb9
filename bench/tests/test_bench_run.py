"""bench/run.py refuses to measure anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, "--workload", "fedyolov3-416.sync", "--seed", "1",
                           "--seconds", "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_exits_non_zero_on_the_cpu_and_prints_no_result():
    out = _run(harness.ROOT, "bench/run.py")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_run_fails_in_a_directory_holding_only_the_benchmark(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, "bench/run.py")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
