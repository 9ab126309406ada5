"""The measuring command refuses to measure where it cannot: on a CPU,
and in a directory that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

from benchtiny import REPO


def run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cd_probe", "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def prints_no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_refuses_a_cpu():
    proc = run_command(REPO)
    assert proc.returncode != 0
    assert prints_no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_fails_without_the_program(tmp_path):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_command(str(tmp_path))
    assert proc.returncode != 0
    assert prints_no_result(proc.stdout)
