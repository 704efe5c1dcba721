"""Finding things by name, the device gate and the table of peaks.

Nothing here knows a cell, a configuration, a traffic kind or a
metric: each is found from ``BENCHMARK.json`` and the files beside
this module, so a new one is added by new files and new entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` (file names may hold dots)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, "bench")

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        cell = load_json(os.path.join(self.bench_dir, "cells",
                                      f"{name}.json"))
        if cell["config"] != self.workload(name)["config"]:
            raise ValueError(f"cell {name!r} names config "
                             f"{cell['config']!r}, BENCHMARK.json "
                             f"{self.workload(name)['config']!r}")
        return cell

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, kind: str):
        return load_module(os.path.join(self.bench_dir, "traffic",
                                        f"{kind}.py"),
                           f"bench_traffic_{kind}")

    def reader(self, metric: str):
        """The reader of ``metric``: ``metrics/<name before the first
        dot>.py``, so ``spmv_roofline.batch`` and a later
        ``spmv_roofline.<cell kind>`` share one file."""
        base = metric.split(".")[0]
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        f"{base}.py"),
                           f"bench_metric_{base}")

    def _reported(self, entries, workload: str, e2e: set) -> list:
        out = []
        for m in entries:
            cells = m.get("workloads")
            if cells is not None:
                if workload in cells:
                    out.append(m)
            elif "moves" not in m or m["moves"] in e2e:
                out.append(m)
        return out

    def end_to_end(self, workload: str) -> list:
        return self._reported(self.spec["end_to_end"], workload, set())

    def per_layer(self, workload: str) -> list:
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return self._reported(self.spec["per_layer"], workload, e2e)


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``device_kind`` ``kind``; an
    unknown kind is an error, never a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def tpu_gate(chips: int) -> dict:
    """The device the run may use: a TPU with at least ``chips`` chips
    whose peaks are known.  Raises ``NoDevice`` otherwise."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoDevice(f"needs a TPU, found {dev.platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, found "
                       f"{len(devices)}")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    try:
        info["peaks"] = peaks(dev.device_kind)
    except KeyError as e:
        raise NoDevice(str(e)) from None
    return info


def use_compile_cache(root: str = ROOT) -> None:
    """JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads it itself), else at
    the fixed path ``.jax_cache/`` in the checkout; every program is
    cached, however short its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_memory_bytes() -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where reported."""
    import jax
    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in jax.local_devices()]
    peaks_seen = [p for p in peaks_seen if p is not None]
    return max(peaks_seen) if peaks_seen else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
