"""Fixtures of the benchmark's CPU tests: a copy of ``BENCHMARK.json``
and ``bench/`` whose deployments are cut to a few thousand edges, and
a stand-in for the chip gate."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_SCALE = {"graphalytics-pr.graph500-22": 9}
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
              "peaks": {"hbm_bytes_per_s": 819e9}}


def copy_bench(dest: str) -> str:
    """``BENCHMARK.json`` and ``bench/`` copied under ``dest``, the
    configurations cut to ``TINY_SCALE``."""
    shutil.copytree(os.path.join(ROOT, "bench"),
                    os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    spec = json.load(open(os.path.join(dest, "BENCHMARK.json")))
    for c in spec["configs"]:
        path = os.path.join(dest, c["file"])
        cfg = json.load(open(path))
        cfg["scale"] = TINY_SCALE[c["name"]]
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return copy_bench(str(tmp_path))


@pytest.fixture
def cpu_gate(monkeypatch):
    """``run.main`` past the chip gate on the CPU, with no persistent
    compile cache written."""
    from bench import harness

    def gate(chips):
        return dict(CPU_DEVICE)

    monkeypatch.setattr(harness, "use_compile_cache", lambda root: None)
    return gate
