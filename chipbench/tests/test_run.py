"""``run.py`` refuses to measure without a TPU, and without the program."""

import json
import os
import shutil
import subprocess
import sys

import tiny

RUN = os.path.join(tiny.BENCH, "run.py")
ARGS = ["--workload", "cbct512.cgls", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, RUN] + ARGS, cwd=tiny.ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert _no_result(out.stdout)
    assert "TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chipbench/run.py"] + ARGS,
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert _no_result(out.stdout)
