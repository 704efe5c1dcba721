"""``bench/run.py`` refuses to run off a TPU, and without the program
beside it, printing no result."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "graph500-22.batch",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_off_a_tpu_with_no_result():
    out = run_bench(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = run_bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
