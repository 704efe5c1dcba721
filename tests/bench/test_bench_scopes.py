"""Device time by the program's named scopes and idle time by its host
spans (``bench/scopes.py``), on hand-made events and on two small
traces recorded on a TPU v5e (``fixtures/``); the readers of the plan
build's phase counters; and the existing metrics, which read as before
on the older trace."""
from __future__ import annotations

import os
import types

import pytest

from bench import harness, scopes
from bench import trace as tr

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
# three solves of a scale-12 batch window, recorded before the program
# named its scopes
OLD = os.path.join(FIXTURES, "batch_v5e.xplane.pb")
# three 20-pass solves of the configuration cut to scale 16 (46,827
# vertices, 1,818,822 arcs), recorded with the scopes and repro.* spans
SCOPED = os.path.join(FIXTURES, "batch_scoped_v5e.xplane.pb")
SCOPED_PASSES = 60
PROGRAM_SCOPES = ("pcpm.scatter", "pcpm.expand", "pcpm.reduce",
                  "pagerank.apply")


@pytest.fixture(scope="module")
def old():
    return scopes.read(OLD)


@pytest.fixture(scope="module")
def scoped():
    return scopes.read(SCOPED)


def test_decoded_ops_and_spans_are_profiledata_s(old):
    ops, spans = tr.read_events(OLD)
    assert sorted(old.spans) == sorted(spans)
    assert list(old.ops) == list(ops)
    for device, events in ops.items():
        mine = old.ops[device]
        assert len(mine) == len(events)
        for (_, s0, e0), (_, s1, e1) in zip(events, mine):
            # ProfileData truncates the picoseconds to whole ns
            assert abs(s0 - s1) < 2 and abs(e0 - e1) < 3
    assert old.start_ns == 1792190013778689793


def test_scope_paths_of_the_old_trace(old):
    paths = {p for events in old.ops.values() for p, _, _ in events}
    assert "jit(run)/while/body/jit(pcpm_scatter)/gather:" in paths
    assert "jit(run)/while/body/jit(pcpm_gather_blocked)/scatter-add:" \
        in paths
    # nothing in it is a program scope yet: all of it is unscoped
    assert {scopes.scope_of(p) for p in paths} == {scopes.UNSCOPED}


def test_total_is_the_busy_time_less_the_while_ops(old):
    ops, spans = tr.read_events(OLD)
    summary = tr.reduce(ops, spans)
    total = sum(scopes.scope_seconds(old).values())
    _, lo, hi = next(s for s in spans if s[0] == tr.WINDOW)
    leaves = tr.leaves(ops["/device:TPU:0"])
    leaf_sum = sum(min(e, hi) - max(s, lo) for _, s, e in leaves
                   if min(e, hi) > max(s, lo)) * 1e-9
    # ProfileData's times are whole ns: half a ns per op apart
    assert total == pytest.approx(leaf_sum, abs=len(leaves) * 1e-9)
    # the while op spans the gaps between its body's ops too
    assert total == pytest.approx(summary.busy_s, rel=1e-4)


def test_idle_gaps_by_span_of_the_old_trace(old):
    summary = tr.reduce(*tr.read_events(OLD))
    idle = scopes.idle_by_span(old)
    assert idle == pytest.approx(dict(summary.idle_gaps), rel=1e-4)
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-4)


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(run)/while/body/jit(pcpm_scatter)/pcpm.scatter/gather:",
     "pcpm.scatter"),
    ("jit(run)/while/body/jit(pcpm_gather_blocked)/pcpm.reduce/"
     "jit(remainder)/rem:", "pcpm.reduce"),
    ("jit(run)/pagerank.apply/while/body/jit(f)/pcpm.expand/gather:",
     "pcpm.expand"),
    ("jit(run)/while/cond/pagerank.apply/lt:", "pagerank.apply"),
    ("jit(run)/while/body/mul:", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_innermost_program_scope(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_scoped_trace_splits_the_pass(scoped):
    summary = tr.reduce(*tr.read_events(SCOPED))
    sec = scopes.scope_seconds(scoped)
    assert set(sec) == set(PROGRAM_SCOPES) | {scopes.UNSCOPED}
    assert sec[scopes.UNSCOPED] < 0.01 * summary.busy_s
    r = types.SimpleNamespace(trace=summary, peaks={},
                              counters={"passes": SCOPED_PASSES})
    pass_ms = harness.Registry().reader("pass_ms.batch").read(r)
    four = sum(sec[s] for s in PROGRAM_SCOPES) * 1e3 / SCOPED_PASSES
    assert four == pytest.approx(pass_ms, rel=0.02)
    assert max(PROGRAM_SCOPES, key=sec.get) == "pcpm.expand"


def test_scoped_trace_idle_is_the_programs(scoped):
    summary = tr.reduce(*tr.read_events(SCOPED))
    idle = scopes.idle_by_span(scoped)
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-4)
    program = sum(v for k, v in idle.items() if k.startswith("repro."))
    assert program >= 0.9 * sum(idle.values())
    names = {n for n, _, _ in scoped.spans}
    assert {"repro.solve", "repro.solve.inputs", "repro.solve.run",
            "repro.solve.readback"} <= names


def hand_made():
    """Two devices, one pass each inside a ``while``; the window cuts
    the first scatter short; the idle gaps lie in the program's
    ``repro.solve.*`` phases inside the benchmark's ``bench.solve``."""
    body = "jit(run)/while/body/"
    ops = [(body[:-6], 10, 90),                           # the while op
           (body + "jit(s)/pcpm.scatter/gather:", 5, 30),
           (body + "jit(g)/pcpm.expand/gather:", 30, 70),
           (body + "jit(g)/pcpm.reduce/scatter-add:", 70, 80),
           (body + "pagerank.apply/mul:", 80, 85),
           ("", 85, 90), ("", 100, 105)]                  # copies
    spans = [("bench.window", 10, 120), ("bench.solve", 10, 120),
             ("repro.solve", 10, 120), ("repro.solve.inputs", 90, 100),
             ("repro.solve.readback", 105, 120)]
    return scopes.Profile({"/device:TPU:0": ops, "/device:TPU:1": ops},
                          spans, 0)


def test_scope_seconds_hand_made():
    sec = scopes.scope_seconds(hand_made())
    assert sec == pytest.approx({
        "pcpm.expand": 40e-9, "pcpm.scatter": 20e-9,
        "pcpm.reduce": 10e-9, "pagerank.apply": 5e-9,
        scopes.UNSCOPED: 10e-9})
    assert list(sec) == sorted(sec, key=sec.get, reverse=True)


def test_idle_gaps_go_to_the_innermost_program_span():
    idle = scopes.idle_by_span(hand_made())
    assert idle == pytest.approx({"repro.solve.inputs": 10e-9,
                                  "repro.solve.readback": 15e-9})


def test_scopes_command_line(capsys):
    assert scopes.main([OLD, "--passes", "60"]) == 0
    out = capsys.readouterr().out
    assert '"unscoped"' in out and '"scope_ms_per_pass"' in out


# ---------------------------------------------------------------------------
# The plan build's phase counters
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("metric,field", [("png_build_s", "png_build_s"),
                                          ("schedule_build_s",
                                           "schedule_build_s")])
def test_plan_phase_readers(metric, field, monkeypatch):
    from repro.core import plan as plan_mod
    reader = harness.Registry().reader(metric)
    stats = plan_mod.PlanCacheStats(**{field: 12.5})
    monkeypatch.setattr(plan_mod, "plan_cache_stats", lambda: stats)
    r = types.SimpleNamespace(trace=None, counters={"plan_build_s": 20.0},
                              peaks={})
    assert reader.read(r) == 12.5
    r.counters = {}                         # a cell that builds no plan
    assert reader.read(r) is None
    # a program that keeps no such counter gives nothing, and no error
    monkeypatch.setattr(plan_mod, "plan_cache_stats",
                        lambda: types.SimpleNamespace(plan_builds=1))
    r.counters = {"plan_build_s": 20.0}
    assert reader.read(r) is None


def test_existing_metrics_read_as_before_on_the_old_trace():
    summary = tr.reduce(*tr.read_events(OLD))
    r = types.SimpleNamespace(
        trace=summary, peaks={"hbm_bytes_per_s": 819e9},
        counters={"passes": 60, "columns": 1, "n": 4096, "m": 65536,
                  "plan_build_s": 1.5})
    reg = harness.Registry()
    got = {m: reg.reader(m).read(r) for m in (
        "pass_ms.batch", "spmv_roofline.batch", "device_idle_pct.batch",
        "plan_build_s")}
    assert got == {"pass_ms.batch": 0.5650729499999999,
                   "spmv_roofline.batch": 0.06726437641242534,
                   "device_idle_pct.batch": 27.0954356668025,
                   "plan_build_s": 1.5}
    assert (summary.busy_s, summary.window_s) == (0.033904377, 0.04650515)
