"""The program's own tracing on the profiler's terms (DESIGN.md §14):
named scopes on the device pass, coarse host phases as profiler
annotations on the profile's clock, build-phase seconds in the plan
cache's counters, and compiles from JAX's own events."""
from __future__ import annotations

import glob
import importlib
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.plan import (PlanConfig, build_plan, clear_plan_cache,
                             plan_cache_stats)
from repro.graphs import generators
from repro.obs import Observability, Tracer, now_ns

pagerank_mod = importlib.import_module("repro.core.pagerank")
backends = importlib.import_module("repro.core.backends")

SCOPES = ("pcpm.scatter", "pcpm.expand", "pcpm.reduce", "pagerank.apply")
SOLVE_PHASES = ("repro.solve", "repro.solve.inputs", "repro.solve.run",
                "repro.solve.readback")


@pytest.fixture(scope="module")
def g():
    return generators.rmat(8, 8, seed=1)


# ---------------------------------------------------------------------------
# Named scopes of the loop body
# ---------------------------------------------------------------------------
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INST = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def _computations(hlo: str) -> dict:
    """``name -> [(instruction line, opcode)]`` of an HLO text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append((line.strip(), _INST.match(line.strip()).group(2)))
    return comps


def _while_body_ops(hlo: str):
    """``(instruction, full op_name or None)`` of every operation the
    ``while`` loop's body and condition run, through nested calls;
    constants and the state's tuple plumbing are left out."""
    comps = _computations(hlo)
    (loop,) = [ln for c in comps.values() for ln, op in c if op == "while"]

    def walk(comp, prefix):
        consts = set()
        for line, op in comps[comp]:
            name = _INST.match(line).group(1)
            m = re.search(r'op_name="([^"]*)"', line)
            path = prefix + "/" + m.group(1) if m else None
            if op == "constant" or (op == "broadcast"
                                    and re.search(r"broadcast\(%?([\w.\-]+)\)",
                                                  line).group(1) in consts):
                consts.add(name)
            elif op == "call":
                yield from walk(re.search(r"to_apply=%?([\w.\-]+)",
                                          line).group(1), path or prefix)
            elif op not in ("parameter", "get-tuple-element", "tuple"):
                yield name, path

    for key in ("body", "condition"):
        comp = re.search(key + r"=%?([\w.\-]+)", loop).group(1)
        yield from walk(comp, "")


def _lowered_loop(kind: str, g):
    sess = repro.open(g, repro.EngineConfig(method="pcpm", part_size=64))
    n = g.num_nodes
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    if kind == "fused":
        loop = pagerank_mod.fused_power_iteration(
            sess.engine, num_iterations=5, tol=1e-9,
            dangling="redistribute")
        args = (vec, vec, vec)
    else:
        loop = pagerank_mod.masked_chunk_stepper(
            sess.engine, chunk=4, dangling="redistribute")
        cols = 3
        pool = jax.ShapeDtypeStruct((n, cols), jnp.float32)
        args = (pool, pool, jax.ShapeDtypeStruct((cols,), jnp.bool_),
                jax.ShapeDtypeStruct((cols,), jnp.float32),
                jax.ShapeDtypeStruct((cols,), jnp.int32), vec)
    return loop.func.lower(*loop.args, *args).as_text(dialect="hlo",
                                                      debug_info=True)


@pytest.mark.parametrize("kind", ["fused", "stepper"])
def test_every_loop_body_op_is_under_a_scope(kind, g):
    ops = list(_while_body_ops(_lowered_loop(kind, g)))
    assert len(ops) > 20
    unscoped = [(n, p) for n, p in ops
                if p is None or not any(s in p.split("/") for s in SCOPES)]
    assert not unscoped, unscoped
    seen = {s for _, p in ops for s in SCOPES if s in p.split("/")}
    assert seen == set(SCOPES)


# ---------------------------------------------------------------------------
# Host phases on the profile's clock
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def profiled(g, tmp_path_factory):
    """One observed ``Session.pagerank()`` and one ``Tracer.span``,
    recorded in a CPU profile: ``(host events, profile start ns,
    flight-recorder records)``."""
    from jax.profiler import ProfileData
    sess = repro.open(g, repro.EngineConfig(method="pcpm", part_size=64,
                                            observe=True))
    sess.pagerank()                      # compile outside the profile
    tracer = Tracer()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(log_dir)
    try:
        sess.pagerank()
        with tracer.span("repro.test.span"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = ProfileData.from_file(path)
    events, start = {}, None
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    records = (sess.obs.recorder.snapshot()
               + tracer.recorder.snapshot())
    sess.obs.close()
    return events, start, records


def test_solve_phases_on_the_host_plane(profiled):
    events, _, _ = profiled
    for name in SOLVE_PHASES:
        assert len(events.get(name, ())) == 1, (name, sorted(events))
    (solve,) = events["repro.solve"]
    children = [events[n][0] for n in SOLVE_PHASES[1:]]
    assert all(solve[0] <= a <= b <= solve[1] for a, b in children)
    assert [a for a, _ in children] == sorted(a for a, _ in children)


@pytest.mark.parametrize("name", ["repro.solve", "repro.test.span"])
def test_span_record_lies_inside_its_annotation(profiled, name):
    """A flight-recorder record, shifted by the profile's start time,
    falls inside the profiler annotation of the same span."""
    events, start, records = profiled
    rec = [r for r in records if r.name == name][-1]   # the profiled one
    ((lo, hi),) = events[name]
    # the record's clock is monotonic + one offset to the epoch taken
    # at import, the profiler's the epoch clock itself: allow the two
    # to drift apart by tens of microseconds
    slack = 50e3
    assert lo - slack <= rec.t_start - start <= rec.t_end - start \
        <= hi + slack
    assert rec.duration_s == pytest.approx((hi - lo) * 1e-9, abs=1e-4)


def test_span_times_are_epoch_nanoseconds():
    tracer = Tracer()
    before = time.time_ns()
    with tracer.span("s"):
        time.sleep(0.01)
    (rec,) = tracer.recorder.snapshot()
    assert isinstance(rec.t_start, int)
    assert abs(rec.t_start - before) < 5e6          # within 5 ms
    assert 0.01 <= rec.duration_s < 1.0
    assert 0 <= now_ns() - rec.t_end < 1e9


# ---------------------------------------------------------------------------
# Build-phase seconds and compiles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method,phases", [
    ("pcpm", ("check_s", "png_build_s", "schedule_build_s")),
    ("pdpr", ("check_s", "schedule_build_s")),
    ("bvgas", ("check_s", "schedule_build_s")),
])
def test_build_phase_seconds(method, phases):
    clear_plan_cache()
    g = generators.rmat(10, 8, seed=5)
    plan = build_plan(g, PlanConfig(method=method, part_size=256))
    stats = plan_cache_stats()
    for field in phases:
        assert getattr(stats, field) > 0, field
    if method != "pcpm":
        assert stats.png_build_s == 0
    assert stats.upload_s == 0            # streams upload on first use
    backends.spmv_fn(plan)
    assert stats.upload_s > 0
    built = {f: getattr(stats, f) for f in
             ("check_s", "png_build_s", "schedule_build_s", "upload_s")}
    plan2 = build_plan(g, PlanConfig(method=method, part_size=256))
    backends.spmv_fn(plan2)
    assert plan2 is plan and stats.plan_hits == 1
    assert {f: getattr(stats, f) for f in built} == built
    clear_plan_cache()
    assert plan_cache_stats().schedule_build_s == 0


def test_compiles_are_recorded_from_jax_events():
    obs = Observability(capacity=64)
    try:
        jax.jit(lambda x: x * 3 + 1)(np.ones(7)).block_until_ready()
        (ev,) = [r for r in obs.recorder.snapshot() if r.name == "compile"]
        assert ev.attrs["duration_s"] > 0 and ev.attrs["fun"]
        assert obs.registry.counter_value("xla_compiles_total") == 1
    finally:
        obs.close()
    jax.jit(lambda x: x - 2)(np.ones(7)).block_until_ready()
    assert obs.registry.counter_value("xla_compiles_total") == 1
