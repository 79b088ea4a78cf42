"""The harness runs only on a TPU: on the CPU it exits non-zero and
prints no result."""
import os
import subprocess
import sys

from bench.tests.conftest import ROOT


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "m6-base.serve.batch", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_alone(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(tmp_path / "bench" / "run.py"),
                        "--workload", "m6-base.serve.batch", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300,
                       cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
