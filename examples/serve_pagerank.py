"""Continuous-batching PageRank query serving demo (DESIGN.md §7/§8).

    PYTHONPATH=src python examples/serve_pagerank.py [--scale 12]

Registers two graphs in a GraphRegistry — one built in-process, one
warm-loaded from the graphs/io.py npz format TOGETHER with its
persisted GraphPlan (so the server process pays an npz read, not an
edge re-sort) — then fires a mixed workload at each: uniform-teleport
queries, personalized queries with per-request tolerances (so slots
converge at different times and the scheduler back-fills freed columns
mid-flight), and on-device top-k queries that ship only k ids+scores
to the host.  Prints the per-query results and the latency/throughput
summary from serve/metrics.py.

The finale is a LIVE GRAPH UPDATE (DESIGN.md §9): with queries still
in flight, an edge batch lands on the kron graph —
``scheduler.apply_delta`` patches the plan's dirty partitions, swaps
the stepper (one re-lower; the admit/extract executables survive), and
the in-flight columns keep iterating straight into the NEW graph's
answers while fresh queries are admitted behind them.

``--push`` runs the forward-push query demo instead (DESIGN.md §11):
the same scheduler front door, but loose-tolerance top-k personalized
queries are routed to the host-side forward-push backend — no device
slot, no batching wait — while tight-tolerance queries on the SAME
scheduler still take the masked chunk stepper.  Prints the per-route
throughput and the top-k agreement between the two routes.

``--gateway`` runs the async front-door demo instead (DESIGN.md §13):
``Session.gateway()`` probes the measured stepper cost and autotunes
the slot-pool size, four submitter threads get futures back
immediately (push-eligible traffic on the worker pool, full-vector
queries interleaved on the device thread), a repeated query is served
bit-identically from the warm-result cache, and a live edge delta
invalidates exactly the dead cache entries while traffic continues.

``--chaos`` runs the resilience demo instead (DESIGN.md §10): the
same serving pool under injected faults — a NaN poisons a slot column
mid-flight (quarantined + re-admitted from its clean seed), a device
step throws (retried), the pool is snapshotted, "killed", and restored
mid-flight — and every answer still matches the fault-free run.

``--observe`` runs the observability demo instead (DESIGN.md §14):
the gateway storm again, but with a flight recorder + metrics
registry attached — every query leaves a well-nested span tree
(intake → backlog → slot/push → terminal → resolve), the plan build
and solve are traced, and the measured-vs-model communication
accountant counts every executed device pass.  Writes the trace
JSONL, a Prometheus metrics snapshot, and the stats JSON into
``--out`` (artifacts a CI run uploads).
"""
import argparse
import json
import os
import tempfile

import numpy as np

import repro
from repro.graphs import generators, io as graph_io
from repro.serve import GraphRegistry, SlotScheduler


def chaos(args):
    from repro.reliability import (FaultInjector, FaultPlan, FaultSpec,
                                   ResilienceConfig, restore_scheduler,
                                   snapshot_scheduler)
    g = generators.rmat(args.scale, 16, seed=7)
    part_size = max(256, g.num_nodes // 64)
    kw = dict(slots=args.slots, method="pcpm", part_size=part_size,
              chunk=4)
    rng = np.random.default_rng(0)
    seeds = []
    for _ in range(args.queries):
        s = np.zeros(g.num_nodes, np.float32)
        s[rng.integers(0, g.num_nodes, size=2)] = 1.0
        seeds.append(s)

    ref = SlotScheduler(g, **kw)
    refs = [ref.submit(s, tol=1e-6, max_iters=300) for s in seeds]
    ref_by_uid = {r.uid: r for r in ref.run_until_drained()}
    print(f"fault-free: {len(refs)} queries served "
          f"(trace_count={ref.trace_count})")

    # same workload, with a NaN poisoning slot 0 mid-flight and a
    # device step exception two chunks later
    inj = FaultInjector(FaultPlan.of([
        FaultSpec("nan_slot", step=2, slot=0),
        FaultSpec("step_error", step=4),
    ]))
    sch = SlotScheduler(
        g, fault_injector=inj,
        resilience=ResilienceConfig(max_queue=4 * args.queries,
                                    max_retries=1, max_step_retries=1),
        **kw)
    uids = [sch.submit(s, tol=1e-6, max_iters=300) for s in seeds]
    for _ in range(6):              # run into both faults...
        sch.step()
    with tempfile.TemporaryDirectory() as td:     # ...then die
        path = os.path.join(td, "sched.npz")
        snapshot_scheduler(sch, path)
        print(f"chaos: snapshot with {sch.active_slots} in flight, "
              f"{sch.queued} queued, faults fired="
              f"{[f.kind for f in inj.fired]}")
        done_before = {r.uid: r for r in sch.completed}
        counters = dict(sch.metrics.counters)     # quarantine/retry
        sch = restore_scheduler(path, g, **kw)    # "new process"
    out = {r.uid: r for r in sch.run_until_drained()}
    out.update(done_before)

    worst = max(float(np.abs(ref_by_uid[a].ranks - out[b].ranks).max())
                for a, b in zip(refs, uids))
    print(f"restored: {len(out)} served, pre-crash counters="
          f"{counters}, trace_count={sch.trace_count}")
    print(f"max |chaos - fault-free| over all queries: {worst:.2e}")
    assert worst <= 1e-6, "chaos run diverged from fault-free answers"
    assert sch.trace_count == 1
    print("resilience demo OK: poisoned slot quarantined + re-served, "
          "step fault retried, restart resumed mid-flight — answers "
          "identical")


def push(args):
    import time

    g = generators.rmat(args.scale, 16, seed=7)
    part_size = max(64, g.num_nodes // 64)
    sch = SlotScheduler(g, slots=args.slots, method="pcpm",
                        part_size=part_size, chunk=4)
    rng = np.random.default_rng(0)
    seeds = []
    for _ in range(args.queries):
        s = np.zeros(g.num_nodes, np.float32)
        s[rng.integers(0, g.num_nodes)] = 1.0
        seeds.append(s)

    results = {}
    for route in ("push", "stepper"):
        # warm the route's compiled path, then time the workload
        sch.submit(seeds[0], top_k=10, tol=1e-3, max_iters=300,
                   route=route)
        sch.run_until_drained()
        t0 = time.perf_counter()
        uids = [sch.submit(s, top_k=10, tol=1e-3, max_iters=300,
                           route=route) for s in seeds]
        sch.run_until_drained()     # push results landed at submit
        dt = time.perf_counter() - t0
        done = {r.uid: r for r in sch.completed}
        results[route] = [done[u] for u in uids]
        iters = np.mean([r.iterations for r in results[route]])
        print(f"{route:8s}: {len(uids)} personalized top-10 queries "
              f"in {dt * 1e3:7.1f}ms ({len(uids) / dt:7.1f} qps, "
              f"mean {iters:.1f} {'sweeps' if route == 'push' else 'iters'})")
    agree = np.mean([
        len(set(map(int, a.top_ids)) & set(map(int, b.top_ids)))
        / len(a.top_ids)
        for a, b in zip(results["push"], results["stepper"])])
    c = sch.metrics.counters
    print(f"push_served={c['push_served']} "
          f"fallbacks={c.get('push_fallbacks', 0)} "
          f"trace_count={sch.trace_count}")
    print(f"top-10 agreement push vs stepper: {agree:.1%}")
    assert agree >= 0.9 and sch.trace_count == 1
    print("push demo OK: same front door, loose-tolerance top-k "
          "queries served host-side without touching a device slot")


def gateway(args):
    """Async front-door demo (DESIGN.md §13): autotuned slot pool,
    concurrent submitters getting futures, warm-result cache hits, and
    a live delta invalidating the cache mid-traffic."""
    import threading
    import time

    g = generators.rmat(args.scale, 16, seed=7)
    part_size = max(64, g.num_nodes // 64)
    sess = repro.open(g, repro.EngineConfig(
        method="pcpm", part_size=part_size, chunk=4, slots=args.slots))
    rng = np.random.default_rng(0)
    nodes = rng.choice(g.num_nodes, size=args.queries, replace=False)

    def one_hot(node):
        s = np.zeros(g.num_nodes, np.float32)
        s[node] = 1.0
        return s

    with sess.gateway() as gw:
        rep = gw.autotune_report
        print(f"autotune: probes(ms)="
              f"{ {b: round(t * 1e3, 2) for b, t in rep.probes.items()} } "
              f"target={rep.target_chunk_s * 1e3:.0f}ms -> B={rep.chosen} "
              f"(session default was {args.slots})")

        # N submitter threads, futures back immediately; half the
        # traffic is push-eligible top-k, half full-vector stepper
        results, lock = [], threading.Lock()

        def client(lo, hi):
            futs = [gw.submit(one_hot(nodes[i]),
                              top_k=10 if i % 2 else None,
                              tol=1e-3 if i % 2 else 1e-5,
                              max_iters=300)
                    for i in range(lo, hi)]
            got = [f.result(timeout=300) for f in futs]
            with lock:
                results.extend(got)

        t0 = time.perf_counter()
        q4 = args.queries // 4
        threads = [threading.Thread(target=client,
                                    args=(i * q4, (i + 1) * q4))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert all(r.error is None for r in results)
        assert len({r.uid for r in results}) == len(results)
        print(f"4 threads, {len(results)} queries in {dt * 1e3:.0f}ms "
              f"({len(results) / dt:.0f} qps), all converged, "
              f"uids unique")

        # a repeat is a warm-result hit: O(k), bit-identical arrays
        r1 = gw.submit(one_hot(nodes[1]), top_k=10,
                       tol=1e-3, max_iters=300).result(timeout=300)
        assert r1.cached and r1.top_ids is not None
        print(f"repeat query: cached={r1.cached} "
              f"(cache: {gw.stats()['cache']})")

        # live delta: plan patched between chunks, cache entries for
        # the outgoing fingerprint dropped atomically
        k = max(4, g.num_edges // 1000)
        delta = repro.GraphDelta.insert(
            np.stack([rng.integers(0, g.num_nodes, k),
                      rng.integers(0, g.num_nodes, k)], axis=1))
        dropped = gw.apply_delta(delta).result(timeout=300)
        r2 = gw.submit(one_hot(nodes[1]), top_k=10,
                       tol=1e-3, max_iters=300).result(timeout=300)
        sch = gw._schedulers["default"]
        print(f"±{k}-edge delta: {dropped} cache entries invalidated, "
              f"repeat recomputed (cached={r2.cached}), "
              f"rebinds={sch.rebind_count}")
        assert not r2.cached
        assert sch.trace_count == 1 + sch.rebind_count
        assert sch.admit_trace_count == 1
    print("gateway demo OK: futures front door, autotuned pool, "
          "warm-result cache with delta invalidation — zero retraces")


def observe(args):
    """Observability demo (DESIGN.md §14): the gateway storm with the
    flight recorder on, then dump the three artifact surfaces — trace
    JSONL, Prometheus text, stats JSON — into ``--out``."""
    import threading
    import time

    g = generators.rmat(args.scale, 16, seed=7)
    part_size = max(64, g.num_nodes // 64)
    sess = repro.open(g, repro.EngineConfig(
        method="pcpm", part_size=part_size, chunk=4, slots=args.slots,
        observe=True))
    res = sess.pagerank(tol=1e-6, num_iterations=200)  # traced solve
    rng = np.random.default_rng(0)
    nodes = rng.choice(g.num_nodes, size=args.queries, replace=False)

    def one_hot(node):
        s = np.zeros(g.num_nodes, np.float32)
        s[node] = 1.0
        return s

    with sess.gateway() as gw:
        results, lock = [], threading.Lock()

        def client(lo, hi):
            futs = [gw.submit(one_hot(nodes[i]),
                              top_k=10 if i % 2 else None,
                              tol=1e-3 if i % 2 else 1e-5,
                              max_iters=300)
                    for i in range(lo, hi)]
            got = [f.result(timeout=300) for f in futs]
            with lock:
                results.extend(got)

        t0 = time.perf_counter()
        q4 = args.queries // 4
        threads = [threading.Thread(target=client,
                                    args=(i * q4, (i + 1) * q4))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert all(r.error is None for r in results)
        # a repeat is a warm-result cache hit — traced as route=cached
        r1 = gw.submit(one_hot(nodes[1]), top_k=10,
                       tol=1e-3, max_iters=300).result(timeout=300)
        assert r1.cached
        prom = gw.metrics_endpoint()
        sch = gw._schedulers["default"]
        assert sch.trace_count == 1 and sch.admit_trace_count == 1

    # ---- verify span trees off the live ring, then dump artifacts
    obs = sess.obs
    recs = obs.recorder.snapshot()
    uids = {r.uid for r in results}
    roots = [r for r in recs if r.name == "query" and r.trace in uids]
    terms = [r for r in recs if r.name == "terminal" and r.trace in uids]
    assert len(roots) == len(uids), (len(roots), len(uids))
    assert len(terms) == len(uids), "exactly one terminal per query"
    for root in roots:
        kids = [r for r in recs if r.parent_id == root.span_id
                and not r.is_event]
        assert all(root.t_start <= k.t_start and k.t_end <= root.t_end
                   for k in kids), "span tree not well-nested"

    os.makedirs(args.out, exist_ok=True)
    trace_path = obs.dump(os.path.join(args.out, "trace.jsonl"))
    prom_path = os.path.join(args.out, "metrics.prom")
    with open(prom_path, "w") as f:
        f.write(prom)
    stats = sess.stats()
    stats_path = os.path.join(args.out, "stats.json")
    with open(stats_path, "w") as f:
        json.dump(stats, f, indent=1, default=str)

    fr = stats["obs"]["flight_recorder"]
    print(f"storm: {len(results)} queries in {dt * 1e3:.0f}ms "
          f"({len(results) / dt:.0f} qps), solve {res.iterations} iters")
    print(f"flight recorder: {fr['recorded']} recorded, "
          f"{fr['dropped']} dropped, {fr['held']} held "
          f"(capacity {fr['capacity']})")
    print(f"span trees: {len(roots)} roots, {len(terms)} terminals — "
          f"well-nested, exactly one terminal each")
    print(f"artifacts: {trace_path} ({fr['held']} records), "
          f"{prom_path} ({len(prom.splitlines())} lines), {stats_path}")
    print("observability demo OK: traced solve + gateway storm, "
          "complete span trees, zero retraces")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--queries", type=int, default=24)
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-injection / recovery demo "
                         "(DESIGN.md §10)")
    ap.add_argument("--push", action="store_true",
                    help="run the forward-push query routing demo "
                         "(DESIGN.md §11)")
    ap.add_argument("--gateway", action="store_true",
                    help="run the async gateway demo (DESIGN.md §13)")
    ap.add_argument("--observe", action="store_true",
                    help="run the observability demo (DESIGN.md §14)")
    ap.add_argument("--out", default="obs-artifacts",
                    help="artifact directory for --observe (trace "
                         "JSONL, Prometheus snapshot, stats JSON)")
    args = ap.parse_args()
    if args.chaos:
        return chaos(args)
    if args.push:
        return push(args)
    if args.gateway:
        return gateway(args)
    if args.observe:
        return observe(args)

    kron = generators.rmat(args.scale, 16, seed=7)
    plaw = generators.power_law(1 << args.scale, 14, seed=3)
    part_size = max(256, kron.num_nodes // 64)

    reg = GraphRegistry(slots=args.slots, method="pcpm",
                        part_size=part_size, chunk=4)
    reg.add("kron", kron)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "plaw.npz")
        plan_path = os.path.join(td, "plaw.plan.npz")
        graph_io.save(path, plaw)
        # persist the preprocessing artifact next to the graph (what a
        # deployment does once, offline)
        repro.build_plan(plaw, repro.PlanConfig(
            method="pcpm", part_size=part_size)).save(plan_path)
        repro.clear_plan_cache()        # simulate a fresh server process
        # warm-loaded: plan read from npz, scheduler compiled up front
        reg.load("plaw", path, plan_path=plan_path)
    stats = repro.plan_cache_stats()
    print(f"registry: {reg.names()}  "
          f"(slots={args.slots}, trace_count="
          f"{[reg.get(n).trace_count for n in reg.names()]}, "
          f"plan builds since load={stats.plan_builds})")

    rng = np.random.default_rng(0)
    for i in range(args.queries):
        name = ("kron", "plaw")[i % 2]
        n = reg.get(name).n
        kind = i % 3
        if kind == 0:
            reg.submit(name, tol=0.0, max_iters=20)
        elif kind == 1:
            seeds = np.zeros(n, np.float32)
            seeds[rng.integers(0, n, size=2)] = 1.0
            reg.submit(name, seeds, tol=(1e-3, 1e-5)[i % 2],
                       max_iters=200)
        else:
            reg.submit(name, top_k=10, tol=1e-4, max_iters=100)

    # a delta lands mid-load: advance one chunk (queries now in
    # flight), patch the kron scheduler, keep serving
    sch = reg.get("kron")
    sch.step()
    inflight = sch.active_slots
    k = max(4, kron.num_edges // 1000)
    ridx = rng.choice(kron.num_edges, size=k, replace=False)
    delta = repro.GraphDelta.of(
        add=np.stack([rng.integers(0, kron.num_nodes, k),
                      rng.integers(0, part_size, k)], axis=1),
        remove=np.stack([kron.src[ridx], kron.dst[ridx]], axis=1))
    sch.apply_delta(delta)
    print(f"kron: applied ±{k}-edge delta with {inflight} queries "
          f"in flight (rebinds={sch.rebind_count}, admit traces="
          f"{sch.admit_trace_count})")

    out = reg.run_until_drained()
    for name, results in out.items():
        sch = reg.get(name)
        # zero retraces under load; the delta costs exactly one
        # stepper re-lower on the graph it touched
        assert sch.trace_count == 1 + sch.rebind_count
        assert sch.admit_trace_count == 1
        print(f"\n--- {name} (n={sch.n}) ---")
        for r in results:
            what = (f"top{len(r.top_ids)}: {r.top_ids[:4]}..."
                    if r.top_ids is not None
                    else f"ranks[:3]={np.round(r.ranks[:3], 6)}")
            print(f"  uid={r.uid:3d} it={r.iterations:3d} "
                  f"conv={str(r.converged):5s} "
                  f"lat={r.latency_s * 1e3:7.1f}ms  {what}")
        s = sch.metrics.summary()
        print(f"  {s['count']} queries, {s['qps']:.1f} qps, "
              f"p50={s['p50_ms']:.1f}ms p99={s['p99_ms']:.1f}ms, "
              f"mean {s['mean_iterations']:.1f} iters")


if __name__ == "__main__":
    main()
