"""The ``graph500-22.relabeled`` cell on the CPU at a few thousand
edges: a whole run through ``run_cell`` past the chip gate is correct
and reports its end-to-end metrics; ranks left in the plan's internal
ids turn ``correct`` false; its per-layer counters read the plan."""
from __future__ import annotations

import contextlib
import json
import types

import pytest

from bench import harness, run
from conftest import CPU_DEVICE

CELL = "graph500-22.relabeled"


def test_relabeled_cell_runs_correct(tiny_root):
    reg = harness.Registry(tiny_root)
    config = reg.config(reg.cell(CELL)["config"])
    assert config["reorder"] == "hybrid" and config["scale"] == 10
    result = run.run_cell(reg, CELL, 2147483713, 0.05, False, CPU_DEVICE)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "solve_s"}
    assert result["checks"]["iterations_off"]["value"] == 0


def test_ranks_left_in_internal_ids_are_not_correct(tiny_root, cpu_gate,
                                                    capsys, monkeypatch):
    """The solve's answers never mapped back from the relabeled ids."""
    import jax.numpy as jnp
    from repro.core import backends

    def identity(plan):
        ids = jnp.arange(plan.num_nodes, dtype=jnp.int32)
        return ids, ids

    monkeypatch.setattr(backends, "reorder_device", identity)
    rc = run.main(["--workload", CELL, "--seed", "2147483717",
                   "--seconds", "0.05", "--trace", "0"], gate=cpu_gate,
                  root=tiny_root)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["rank_l1"]["value"] > 1e-3


def test_relabeled_window_counts_the_plans_r(tiny_root):
    """The window's ``compression_r`` is the plan's r, and the readers
    of the cell's new metrics read the window and the program's
    counters."""
    from repro.core.plan import clear_plan_cache
    reg = harness.Registry(tiny_root)
    cell = reg.cell(CELL)
    kind = reg.traffic(cell["kind"])
    ctx = types.SimpleNamespace(cell=cell, config=reg.config(cell["config"]),
                                seed=2147483723, log=lambda msg: None,
                                span=lambda name: contextlib.nullcontext())
    clear_plan_cache()
    state = kind.setup(ctx)
    win = kind.window(state, 0.0)
    r = win["counters"]["compression_r"]
    assert r == state.sess.plan.compression_ratio
    reader = types.SimpleNamespace(trace=None, counters=win["counters"],
                                   peaks={})
    assert reg.reader("compression_r.relabeled").read(reader) == r
    assert reg.reader("reorder_s").read(reader) > 0
    verdict = kind.check(state)
    assert run.is_correct(verdict), verdict
    clear_plan_cache()


@pytest.mark.parametrize("metric", ["reorder_s", "compression_r.relabeled"])
def test_new_readers_give_nothing_without_a_count(metric, monkeypatch):
    """A program without the counter (no ``reorder_s`` field) or a
    window without ``compression_r`` gives no number."""
    from repro.core import plan
    monkeypatch.setattr(plan, "plan_cache_stats",
                        lambda: types.SimpleNamespace())
    r = types.SimpleNamespace(trace=None, peaks={},
                              counters={"plan_build_s": 1.0})
    assert harness.Registry().reader(metric).read(r) is None
