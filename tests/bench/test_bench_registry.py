"""The harness is driven by data: a cell, a configuration and a
per-layer metric are added by new files and new entries in
``BENCHMARK.json``, with no existing file edited."""
from __future__ import annotations

import hashlib
import json
import os
import types

import pytest

from bench import harness, run
from conftest import CPU_DEVICE

SPEC = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))


def digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def add_cell(root):
    """A new deployment (batch PageRank at damping 0.5 on a smaller
    graph), a cell of it and a per-layer metric, as new files only."""
    bench = os.path.join(root, "bench")
    base = json.load(open(os.path.join(
        bench, "configs", "graphalytics-pr.graph500-22.json")))
    cfg = dict(base, name="pr-half.graph500-8", scale=8, damping=0.5)
    with open(os.path.join(bench, "configs", "pr-half.graph500-8.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "cells", "graph500-8.batch.json"),
              "w") as f:
        json.dump({"config": "pr-half.graph500-8", "kind": "batch",
                   "limits": {"rank_l1": 1e-5, "iterations_off": 0}}, f)
    with open(os.path.join(bench, "metrics", "solves.py"), "w") as f:
        f.write("def read(r):\n    return r.counters.get('passes')\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({
        "name": "pr-half.graph500-8", "source": "test",
        "file": "bench/configs/pr-half.graph500-8.json",
        "reduced": ["scale", "damping"], "why": "test"})
    spec["workloads"].append({
        "name": "graph500-8.batch", "config": "pr-half.graph500-8",
        "traffic": "batch", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("graph500-8.batch")
    spec["per_layer"].append({
        "name": "solves.new", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "fused loop",
        "moves": "solve_s", "workloads": ["graph500-8.batch"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


def test_new_cell_config_and_metric_are_found_with_no_file_edited(
        tiny_root):
    before = digests(os.path.join(tiny_root, "bench"))
    add_cell(tiny_root)
    after = digests(os.path.join(tiny_root, "bench"))
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/pr-half.graph500-8.json", "cells/graph500-8.batch.json",
        "metrics/solves.py"}

    reg = harness.Registry(tiny_root)
    assert reg.config("pr-half.graph500-8")["damping"] == 0.5
    assert [m["name"] for m in reg.end_to_end("graph500-8.batch")] == [
        "setup_s", "solve_s"]
    assert [m["name"] for m in reg.per_layer("graph500-8.batch")] == [
        "plan_build_s", "solves.new"]
    r = types.SimpleNamespace(trace=None, counters={"passes": 40},
                              peaks={})
    assert reg.reader("solves.new").read(r) == 40

    result = run.run_cell(reg, "graph500-8.batch", 4, 0.05, False,
                          CPU_DEVICE)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "solve_s"}


def test_metrics_each_cell_reports():
    reg = harness.Registry()
    assert [m["name"] for m in reg.end_to_end("graph500-22.batch")] == [
        "setup_s", "solve_s"]
    for w in SPEC["workloads"]:
        layer = reg.per_layer(w["name"])
        assert layer, w["name"]
        e2e = {m["name"] for m in reg.end_to_end(w["name"])}
        assert all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    reader = harness.Registry().reader(metric)
    r = types.SimpleNamespace(trace=None, counters={}, peaks={})
    assert reader.read(r) is None      # nothing to read: no number


def test_metrics_of_one_quantity_share_a_reader():
    """``<quantity>.<suffix>`` is read by ``metrics/<quantity>.py``: a
    later cell's ``spmv_roofline.<kind>`` needs no copy of it."""
    reg = harness.Registry()
    assert reg.reader("spmv_roofline.batch").__file__ == reg.reader(
        "spmv_roofline.backlog").__file__
    assert reg.reader("plan_build_s").__file__.endswith(
        os.path.join("metrics", "plan_build_s.py"))


def test_every_cell_and_config_has_its_file():
    reg = harness.Registry()
    for w in SPEC["workloads"]:
        cell = reg.cell(w["name"])
        reg.config(cell["config"])
        reg.traffic(cell["kind"])


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        harness.peaks("cpu")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
