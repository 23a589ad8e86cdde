"""bench/run.py refuses to measure where it cannot: without a TPU, and in
a directory that holds the benchmark and not the program."""
import os
import shutil
import subprocess
import sys

import rehearse

ROOT = rehearse.ROOT
CELL = rehearse.common.benchmark()["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", "2147483999", "--seconds", "2",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(rehearse.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
