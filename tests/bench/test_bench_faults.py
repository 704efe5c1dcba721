"""``correct`` comes out false when the timed path is broken underneath
a whole run (past the chip gate, on the CPU, at a few thousand edges),
and when the bfloat16 control stands in for the program."""
from __future__ import annotations

import json

import numpy as np
import pytest

import repro
from bench import control, harness, run


def run_json(capsys, root, gate, workload):
    rc = run.main(["--workload", workload, "--seed", "2147483699",
                   "--seconds", "0.05", "--trace", "0"], gate=gate,
                  root=root)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def unchanged(ranks):
    """What a step that returns its state unchanged leaves: the start."""
    n = ranks.shape[0]
    return np.full(n, 1.0 / n, np.float32)


def half_left_out(ranks):
    """Half of the vertices never updated."""
    x = np.array(ranks)
    x[x.shape[0] // 2:] = unchanged(ranks)[x.shape[0] // 2:]
    return x


def altered(ranks):
    """One answer altered where it is made: the top vertex loses its
    rank."""
    x = np.array(ranks)
    x[np.argmax(x)] = 0.0
    return x


@pytest.mark.parametrize("fault", [None, unchanged, half_left_out,
                                   altered])
def test_batch_faults(fault, tiny_root, cpu_gate, capsys, monkeypatch):
    if fault is not None:
        solve = repro.Session.pagerank

        def broken(self, **kw):
            res = solve(self, **kw)
            res.ranks = fault(np.asarray(res.ranks))
            return res

        monkeypatch.setattr(repro.Session, "pagerank", broken)
    out = run_json(capsys, tiny_root, cpu_gate, "graph500-22.batch")
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", ["graph500-22.batch"])
def test_bf16_control_fails_the_check(workload, tiny_root):
    checks = control.read_control(harness.Registry(tiny_root), workload,
                                  2147483701)
    assert any(c["value"] > c["limit"] for c in checks), checks

