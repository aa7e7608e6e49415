"""The entry point prints no result where it cannot measure: without a
TPU, and in a directory that holds only ``BENCHMARK.json`` and the
benchmark's own files."""
import os
import shutil
import subprocess
import sys

from bench import lib

ARGS = ["--workload", "vgg16.b32", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(lib.ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert '"correct"' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(lib.SPEC, tmp_path)
    shutil.copytree(lib.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
