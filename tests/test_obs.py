"""Observability layer tests (DESIGN.md §14).

Four instrument groups, each with exact-semantics unit tests, then the
integration storms:

- metrics registry: counter monotonicity, le-INCLUSIVE histogram
  buckets with exact cumulative exposition, kind-conflict errors,
  Prometheus text format down to the line.
- tracer/flight recorder: explicit-parent nesting, bounded ring with
  drop accounting, exactly-once ``end()``, JSONL dump format.
- plan stream accounting: bytes counted from real plan geometry vs
  the paper's §V model — pcpm must land within 2x of eq. 5, and the
  per-stream breakdown must reconcile.
- the serving integration: a PR 9-shaped concurrent mixed push/stepper
  storm with observability ON must yield one complete, well-nested
  span tree per query with exactly one terminal event, keep
  ``trace_count == 1``, and cost < 5% qps vs observability OFF.
"""
import json
import threading
import time

import numpy as np
import pytest

import repro
from repro.core.plan import PlanConfig, build_plan, clear_plan_cache
from repro.graphs import generators
from repro.obs import (FlightRecorder, MetricsRegistry, Observability,
                       QuerySpans, Tracer, measure_plan, vs_model)
from repro.reliability import (FaultInjector, FaultPlan, FaultSpec,
                               ResilienceConfig)
from repro.serve import SlotScheduler
from repro.serve.metrics import ServeMetrics

SMALL = dict(method="pcpm", part_size=64, chunk=4)


@pytest.fixture(scope="module")
def g():
    return generators.rmat(8, 8, seed=1)


def _seed(g, at=3):
    s = np.zeros(g.num_nodes, np.float32)
    s[at % g.num_nodes] = 1.0
    s[(at * 7 + 1) % g.num_nodes] = 1.0
    return s


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
class TestMetricsRegistry:
    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total", "help", kind="a")
        c.inc()
        c.inc(3)
        assert reg.counter_value("x_total", kind="a") == 4
        with pytest.raises(ValueError, match="monotone"):
            c.inc(-1)
        assert c.value == 4

    def test_labels_are_order_insensitive(self):
        reg = MetricsRegistry()
        reg.counter("t", a="1", b="2").inc()
        reg.counter("t", b="2", a="1").inc()
        assert reg.counter_value("t", a="1", b="2") == 2
        assert len(reg.family_items("t")) == 1

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")

    def test_unknown_reads_as_zero(self):
        assert MetricsRegistry().counter_value("nope") == 0.0
        assert MetricsRegistry().family_items("nope") == []

    def test_gauge_levels(self):
        reg = MetricsRegistry()
        ga = reg.gauge("depth")
        ga.set(5)
        ga.inc()
        ga.dec(3)
        assert ga.value == 3

    def test_histogram_le_inclusive_exact(self):
        """A value EQUAL to an upper bound lands in that bucket
        (Prometheus ``le`` semantics) and exposed counts are
        cumulative — checked against a hand-computed table."""
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.1, 0.1, 0.5, 1.0, 7.0, 11.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["buckets"] == [(0.1, 2), (1.0, 4), (10.0, 5),
                                   ("+Inf", 6)]
        assert snap["count"] == 6
        assert snap["sum"] == pytest.approx(19.7)

    def test_histogram_rejects_unsorted_bounds(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="ascending"):
            reg.histogram("h", buckets=(1.0, 0.5))

    def test_prometheus_text_exact(self):
        reg = MetricsRegistry()
        reg.counter("ev_total", "events", event="a").inc(2)
        reg.gauge("depth", "queue depth").set(3)
        h = reg.histogram("lat_seconds", "latency", buckets=(0.5, 2.0))
        h.observe(0.5)
        h.observe(1.0)
        text = reg.prometheus_text()
        assert "# HELP ev_total events\n# TYPE ev_total counter\n" \
               'ev_total{event="a"} 2\n' in text
        assert "depth 3\n" in text
        assert 'lat_seconds_bucket{le="0.5"} 1' in text
        assert 'lat_seconds_bucket{le="2"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_sum 1.5" in text
        assert "lat_seconds_count 2" in text

    def test_render_merges_with_extra_labels(self):
        from repro.obs import render_prometheus
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.counter("q_total").inc(1)
        r2.counter("q_total").inc(5)
        text = render_prometheus([(r1, {"graph": "a"}),
                                  (r2, {"graph": "b"}),
                                  (r1, {"graph": "dup"})])   # deduped
        assert 'q_total{graph="a"} 1' in text
        assert 'q_total{graph="b"} 5' in text
        assert "dup" not in text

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("e_total", event='say "hi"\n').inc()
        text = reg.prometheus_text()
        assert r'event="say \"hi\"\n"' in text


# ---------------------------------------------------------------------------
# Tracer / flight recorder
# ---------------------------------------------------------------------------
class TestTracer:
    def test_explicit_parent_nesting(self):
        tr = Tracer(FlightRecorder(16))
        root = tr.start("query", trace=7)
        child = root.child("slot", slot=2)
        child.end(iterations=5)
        root.end()
        recs = tr.recorder.snapshot()
        assert [r.name for r in recs] == ["slot", "query"]  # end order
        slot, query = recs
        assert slot.parent_id == query.span_id
        assert slot.trace == query.trace == 7
        assert slot.attrs == {"slot": 2, "iterations": 5}
        assert query.t_start <= slot.t_start <= slot.t_end <= query.t_end

    def test_end_exactly_once(self):
        tr = Tracer(FlightRecorder(16))
        sp = tr.start("x")
        sp.end()
        sp.end()
        sp.end(status="error")
        assert len(tr.recorder) == 1
        assert tr.double_ends == 2

    def test_ring_bounded_with_drop_accounting(self):
        tr = Tracer(FlightRecorder(4))
        for i in range(10):
            tr.event("e", i=i)
        recs = tr.recorder.snapshot()
        assert len(recs) == 4
        assert [r.attrs["i"] for r in recs] == [6, 7, 8, 9]  # oldest out
        assert tr.recorder.recorded == 10
        assert tr.recorder.dropped == 6

    def test_span_contextmanager_error_status(self):
        tr = Tracer(FlightRecorder(16))
        with pytest.raises(RuntimeError):
            with tr.span("risky"):
                raise RuntimeError("boom")
        (rec,) = tr.recorder.snapshot()
        assert rec.status == "error"
        assert "boom" in rec.attrs["error"]

    def test_jsonl_dump_format(self, tmp_path):
        tr = Tracer(FlightRecorder(8))
        tr.event("a", k=1)
        with tr.span("b", trace=3):
            pass
        path = tr.recorder.dump(str(tmp_path / "f.jsonl"))
        lines = open(path).read().splitlines()
        header = json.loads(lines[0])
        assert header == {"schema": 2, "recorded": 2, "dropped": 0,
                          "capacity": 8, "held": 2}
        rows = [json.loads(ln) for ln in lines[1:]]
        assert [r["name"] for r in rows] == ["a", "b"]
        assert rows[0]["t0"] == rows[0]["t1"]          # event
        assert rows[1]["trace"] == 3
        assert set(rows[0]) == {"name", "span", "parent", "trace",
                                "t0", "t1", "status", "attrs"}

    def test_query_spans_retry_and_terminal(self):
        tr = Tracer(FlightRecorder(32))
        qs = QuerySpans(tr, tr.start("query"))
        qs.bind(42)
        qs.start_child("slot", slot=0)
        qs.start_child("slot", slot=1)     # re-admit: closes the first
        qs.finish(iterations=9)
        recs = tr.recorder.snapshot()
        by = {}
        for r in recs:
            by.setdefault(r.name, []).append(r)
        assert [r.status for r in by["slot"]] == ["retry", "ok"]
        assert len(by["terminal"]) == 1
        assert all(r.trace == 42 for r in recs)
        assert by["query"][0].status == "ok"           # root recorded

    def test_gateway_owned_root_ends_at_resolve(self):
        tr = Tracer(FlightRecorder(32))
        qs = QuerySpans(tr, tr.start("query"), gateway_owned=True)
        qs.bind(1)
        qs.finish()                        # terminal, root still open
        assert "query" not in {r.name for r in tr.recorder.snapshot()}
        qs.resolve()
        names = [r.name for r in tr.recorder.snapshot()]
        assert names.count("query") == 1 and "resolve" in names
        qs.resolve()                       # idempotent
        assert [r.name for r in tr.recorder.snapshot()
                ].count("query") == 1


# ---------------------------------------------------------------------------
# Plan stream accounting
# ---------------------------------------------------------------------------
class TestCommAccounting:
    def test_pcpm_measured_within_2x_of_model(self):
        """Acceptance bound: at scale 16 the DRAM-stream bytes measured
        off the real plan geometry must land within 2x of the paper's
        eq. 5 prediction (padding + the bins round trip are the honest
        gap, quantified in DESIGN.md §14)."""
        g = generators.rmat(16, 16, seed=3)
        plan = build_plan(g, PlanConfig(method="pcpm", part_size=4096))
        cmp_ = vs_model(plan)
        assert cmp_["method"] == "pcpm"
        assert 0.5 <= cmp_["ratio"] <= 2.0, cmp_
        # breakdown reconciles: stream sum == headline number
        meas = measure_plan(plan)
        assert sum(meas.dram.values()) == meas.dram_bytes
        assert meas.dram_bytes == cmp_["measured_bytes_per_iter"]

    def test_all_methods_measurable(self):
        g = generators.rmat(10, 8, seed=2)
        for method in ("pcpm", "pdpr", "bvgas"):
            plan = build_plan(g, PlanConfig(method=method,
                                            part_size=256))
            cmp_ = vs_model(plan)
            assert cmp_["measured_bytes_per_iter"] > 0
            assert cmp_["model_bytes_per_iter"] > 0
            assert np.isfinite(cmp_["ratio"])

    def test_multi_vector_amortizes_index_streams(self):
        """ncols multiplies only the VALUE streams; the index streams
        are read once per pass, so bytes/column strictly decreases —
        the multi-vector amortization the serving stack banks on."""
        g = generators.rmat(10, 8, seed=2)
        plan = build_plan(g, PlanConfig(method="pcpm", part_size=256))
        b1 = measure_plan(plan, ncols=1).dram_bytes
        b8 = measure_plan(plan, ncols=8).dram_bytes
        assert b1 < b8 < 8 * b1


# ---------------------------------------------------------------------------
# ServeMetrics single-home + reconciliation
# ---------------------------------------------------------------------------
class TestServeMetricsReconcile:
    def test_duplicate_terminal_raises(self):
        m = ServeMetrics()
        m.submitted(1)
        m.completed(1, iterations=3, converged=True)
        with pytest.raises(RuntimeError, match="duplicate terminal"):
            m.completed(1, iterations=3, converged=True)

    def test_counters_is_derived_view(self):
        m = ServeMetrics()
        m.incr("rejected", 2)
        assert m.counters["rejected"] == 2
        assert m.counters["never_bumped"] == 0
        # single home: the registry IS the storage
        assert m.registry.counter_value("serve_events_total",
                                        event="rejected") == 2

    def test_reconcile_catches_drift(self):
        """A counter bumped without its terminal — the double-home
        bug class this layer kills — must be NAMED by reconcile()."""
        m = ServeMetrics()
        m.submitted(1)
        m.incr("rejected")
        m.completed(1, iterations=0, converged=False,
                    error="rejected: queue full")
        m.reconcile()                       # consistent: passes
        m.incr("rejected")                  # drift: counter w/o trace
        with pytest.raises(AssertionError, match="rejected"):
            m.reconcile()

    def test_reconcile_routes(self):
        m = ServeMetrics()
        for uid, route, ev in ((1, "push", "push_served"),
                               (2, "cached", "cache_hits")):
            m.submitted(uid)
            m.incr(ev)
            m.completed(uid, iterations=1, converged=True, route=route)
        out = m.reconcile()
        assert out["push_served"] == 1 and out["cache_hits_served"] == 1


# ---------------------------------------------------------------------------
# Plan events + session wiring
# ---------------------------------------------------------------------------
class TestPlanEvents:
    def test_build_and_cache_hit_events(self, g):
        clear_plan_cache()
        obs = Observability(capacity=64)
        try:
            cfg = PlanConfig(method="pcpm", part_size=64)
            build_plan(g, cfg)
            build_plan(g, cfg)              # second call: cache hit
            names = [r.name for r in obs.recorder.snapshot()]
            assert "plan_build" in names and "plan_cache_hit" in names
            assert obs.registry.counter_value(
                "plan_events_total", event="plan_build") == 1
            assert obs.registry.counter_value(
                "plan_events_total", event="plan_cache_hit") == 1
        finally:
            obs.close()

    def test_closed_bundle_detaches(self, g):
        clear_plan_cache()
        obs = Observability(capacity=64)
        obs.close()
        build_plan(g, PlanConfig(method="pcpm", part_size=64))
        assert "plan_build" not in {r.name
                                    for r in obs.recorder.snapshot()}

    def test_patch_emits_plan_patch_event(self, g):
        from repro.stream import GraphDelta
        sess = repro.open(g, repro.EngineConfig(**SMALL, observe=True))
        rng = np.random.default_rng(0)
        delta = GraphDelta.insert(
            np.stack([rng.integers(0, g.num_nodes, 8),
                      rng.integers(0, g.num_nodes, 8)], axis=1))
        sess.apply_delta(delta)
        names = [r.name for r in sess.obs.recorder.snapshot()]
        assert "plan_patch" in names and "session_delta" in names


class TestSessionObserve:
    def test_observe_idempotent_and_stats(self, g):
        sess = repro.open(g, repro.EngineConfig(**SMALL))
        assert sess.obs is None
        obs = sess.observe()
        assert sess.observe() is obs
        res = sess.pagerank(num_iterations=5)
        st = sess.stats()
        assert st["plan_cache"]["plan_builds"] >= 1
        assert set(st["obs"]) == {"metrics", "flight_recorder"}
        assert st["obs"]["flight_recorder"]["recorded"] >= 1
        (solve,) = [r for r in obs.recorder.snapshot()
                    if r.name == "repro.solve"]
        assert solve.attrs["iterations"] == res.iterations

    def test_config_observe_traces_build_and_solve(self):
        clear_plan_cache()
        g2 = generators.rmat(8, 8, seed=9)
        sess = repro.open(g2, repro.EngineConfig(**SMALL, observe=True))
        sess.pagerank(num_iterations=3)
        names = [r.name for r in sess.obs.recorder.snapshot()]
        # the bundle attaches BEFORE the plan builds, so the session's
        # own preprocessing is on the record
        assert "plan_build" in names and "repro.solve" in names

    def test_crash_dump_on_quarantine(self, g, tmp_path):
        """PR 6's resilience path is the forensics moment: a poisoned
        slot that exhausts retries must leave a flight-recorder file
        behind."""
        obs = Observability(capacity=256, dump_dir=str(tmp_path))
        try:
            inj = FaultInjector(FaultPlan.of(
                [FaultSpec("nan_slot", step=2, slot=0)]))
            sch = SlotScheduler(
                g, slots=1, fault_injector=inj, obs=obs,
                resilience=ResilienceConfig(max_retries=0), **SMALL)
            sch.submit(_seed(g), tol=1e-6, max_iters=300)
            sch.run_until_drained()
            assert sch.metrics.counters["quarantined"] == 1
            dumps = list(tmp_path.glob("flight-*.jsonl"))
            assert len(dumps) == 1
            lines = dumps[0].read_text().splitlines()
            assert json.loads(lines[0])["schema"] == 2
            assert any(json.loads(ln)["name"] == "crash_dump"
                       for ln in lines[1:])
            assert obs.registry.counter_value("crash_dumps_total") == 1
        finally:
            obs.close()

    def test_snapshot_parks_trace_beside_state(self, g, tmp_path):
        from repro.reliability.snapshot import snapshot_scheduler
        obs = Observability(capacity=256)
        try:
            sch = SlotScheduler(g, slots=1, obs=obs, **SMALL)
            sch.submit(_seed(g), tol=1e-6, max_iters=300)
            sch.step()
            path = str(tmp_path / "state.npz")
            snapshot_scheduler(sch, path)
            trace = tmp_path / "state.npz.trace.jsonl"
            assert trace.exists()
            rows = [json.loads(ln)
                    for ln in trace.read_text().splitlines()[1:]]
            assert any(r["name"] == "snapshot" for r in rows)
        finally:
            obs.close()


# ---------------------------------------------------------------------------
# The PR 9 storm with observability on
# ---------------------------------------------------------------------------
def _storm(sch, *, threads=6, per=20):
    """Mixed push/stepper storm against a free-running device thread —
    the exact thread-ownership shape of test_serve_accounting's PR 9
    regression.  Returns (uids, elapsed_s)."""
    uids, lock, done = [], threading.Lock(), threading.Event()
    errors = []
    g = sch.g

    def submitter(i):
        mine = []
        for j in range(per):
            if (i + j) % 2:
                mine.append(sch.submit(_seed(g, at=i * 7 + j),
                                       top_k=8, tol=1e-2,
                                       max_iters=300))
            else:
                mine.append(sch.submit(_seed(g, at=i * 5 + j),
                                       tol=1e-5, max_iters=300))
        with lock:
            uids.extend(mine)

    def device_loop():
        try:
            while not done.is_set() or sch.queued or sch.active_slots:
                sch.step()
        except Exception as exc:   # noqa: BLE001
            errors.append(exc)

    t0 = time.perf_counter()
    dev = threading.Thread(target=device_loop)
    dev.start()
    ts = [threading.Thread(target=submitter, args=(i,))
          for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    done.set()
    dev.join(timeout=120)
    elapsed = time.perf_counter() - t0
    assert not dev.is_alive() and not errors
    return uids, elapsed


class TestObservedStorm:
    def test_storm_span_trees_complete_and_well_nested(self, g):
        """Every query in a concurrent mixed storm gets a COMPLETE span
        tree: one root, exactly one terminal event, every child span
        closed and nested inside the root interval — and the stepper
        still compiled exactly once."""
        obs = Observability(capacity=65536)
        try:
            sch = SlotScheduler(g, slots=4, obs=obs, **SMALL)
            uids, _ = _storm(sch)
            assert len(uids) == 120
            sch.metrics.reconcile()
            by_trace = {}
            for r in obs.recorder.snapshot():
                by_trace.setdefault(r.trace, []).append(r)
            assert obs.recorder.dropped == 0    # ring sized for storm
            for uid in uids:
                recs = by_trace[uid]
                roots = [r for r in recs if r.name == "query"]
                terms = [r for r in recs if r.name == "terminal"]
                assert len(roots) == 1, (uid, [r.name for r in recs])
                assert len(terms) == 1, (uid, [r.name for r in recs])
                root = roots[0]
                for r in recs:
                    if r.span_id == root.span_id:
                        continue
                    # well-nested: inside the root's interval, and the
                    # parent chain reaches the root
                    assert root.t_start <= r.t_start
                    assert r.t_end <= root.t_end, (uid, r.name)
                    assert r.parent_id is not None
                # every non-push query passed through queue+slot or
                # push — never both served paths
                names = {r.name for r in recs}
                assert ("push" in names) != ("slot" in names), names
            assert sch.trace_count == 1
            assert sch.admit_trace_count == 1
        finally:
            obs.close()

    def test_observed_storm_qps_within_5pct(self):
        """The acceptance bound: observability ON costs < 5% qps on a
        device-bound storm (scale 12 — chunk compute dominates, the
        regime the serving stack actually runs in; on toy graphs where
        a device step is microseconds, ~20 us of span records per
        query is a measurable slice of nothing).  Best-of-N with
        ALTERNATING trial order on shared pre-compiled schedulers, so
        neither compile time nor CPU warm-up bias either side."""
        import gc
        g_big = generators.rmat(12, 8, seed=1)
        # the production-default ring (8192) comfortably holds a storm
        # (~1k records) — an oversized ring would just hand the GC a
        # bigger live set to sweep mid-trial and measure THAT instead
        obs = Observability(capacity=8192)
        try:
            kw = dict(method="pcpm", part_size=1024, chunk=4)
            sch_off = SlotScheduler(g_big, slots=4, **kw)
            sch_on = SlotScheduler(g_big, slots=4, obs=obs, **kw)
            _storm(sch_off, threads=2, per=5)     # warm both paths
            _storm(sch_on, threads=2, per=5)
            best = {"off": 0.0, "on": 0.0}
            for i in range(4):
                pairs = [("off", sch_off), ("on", sch_on)]
                for key, sch in (pairs if i % 2 == 0
                                 else reversed(pairs)):
                    gc.collect()       # garbage from PRIOR trials is
                    #                    not this trial's overhead
                    uids, dt = _storm(sch)
                    best[key] = max(best[key], len(uids) / dt)
            assert best["on"] >= 0.95 * best["off"], best
        finally:
            obs.close()


class TestGatewayObserved:
    def test_gateway_roots_cover_resolution(self, g):
        """Gateway-owned roots end at future resolution: every uid's
        recorded root must contain its terminal event, and the three
        serve routes (stepper / cache / push) all leave exactly one
        terminal."""
        sess = repro.open(g, repro.EngineConfig(**SMALL, observe=True))
        obs = sess.obs
        gw = sess.gateway(autotune=False, slots=2)
        with gw:
            f1 = gw.submit(tol=1e-3, max_iters=300, top_k=5)
            r1 = f1.result(timeout=120)
            f2 = gw.submit(tol=1e-3, max_iters=300, top_k=5)  # cached
            r2 = f2.result(timeout=120)
            f3 = gw.submit(_seed(g), tol=1e-2, max_iters=300,
                           top_k=5)                           # push
            r3 = f3.result(timeout=120)
        assert r1.converged and r2.error is None and r3.error is None
        by = {}
        for r in obs.recorder.snapshot():
            by.setdefault(r.trace, []).append(r)
        for uid in (r1.uid, r2.uid, r3.uid):
            recs = by[uid]
            roots = [r for r in recs if r.name == "query"]
            terms = [r for r in recs if r.name == "terminal"]
            resolves = [r for r in recs if r.name == "resolve"]
            assert len(roots) == len(terms) == len(resolves) == 1
            assert roots[0].t_start <= terms[0].t_start \
                <= roots[0].t_end
        # route accounting survived the obs plumbing
        sch = next(iter(gw._schedulers.values()))
        rec = sch.metrics.reconcile()
        assert rec["cache_hits_served"] == 1
        assert rec["push_served"] == 1

    def test_metrics_endpoint_scrape(self, g):
        sess = repro.open(g, repro.EngineConfig(**SMALL, observe=True))
        gw = sess.gateway(autotune=False, slots=2)
        with gw:
            gw.submit(tol=1e-3, max_iters=300, top_k=5).result(
                timeout=120)
            text = gw.metrics_endpoint()
        assert "# TYPE serve_terminals_total counter" in text
        assert 'serve_terminals_total{graph="default"} 1' in text
        assert "gateway_cache_entries" in text
        assert "xla_compiles_total" in text     # obs registry merged
        assert "trace_count" in text
