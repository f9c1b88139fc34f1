"""bench/run.py refuses to run without a TPU, or without the program beside it."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "ptychonn_repo.pfs", "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_a_device_that_is_not_a_tpu():
    p = _run(ROOT, ROOT / "bench" / "run.py")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode != 0
    assert p.stdout == ""
