"""A run that finds no TPU, or no program, exits non-zero and prints no
result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.cells import ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig3_grid",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.strip().splitlines()[-1:]:
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not isinstance(obj, dict), "printed a result line"


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p.stdout)


@pytest.mark.parametrize("platforms", ["cpu", ""])
def test_benchmark_files_alone_no_result(tmp_path, platforms):
    """A directory holding only BENCHMARK.json and the benchmark's files
    has no program to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS=platforms or "cpu")
    env.pop("PYTHONPATH", None)
    p = _run(tmp_path, env)
    assert p.returncode != 0
    _no_result(p.stdout)
