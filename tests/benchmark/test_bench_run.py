"""The command as the driver runs it, where it must refuse to measure."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from benchmark import harness

ARGS = ["--workload", "cas_register.10k", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def run_cmd(cwd) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    p = run_cmd(harness.REPO)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A checkout that holds only BENCHMARK.json and the files under
    ``paths`` has no program to measure."""
    spec = json.loads(harness.SPEC.read_text())
    shutil.copy(harness.SPEC, tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(harness.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".cache",
                                                      "__pycache__"))
    p = run_cmd(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
