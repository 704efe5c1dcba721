"""Quickstart: Partition-Centric PageRank through the Session API.

    PYTHONPATH=src python examples/quickstart.py [--scale 16] [--serve]

Builds a Graph500-style Kronecker graph and opens one ``repro.open``
Session per engine: the session resolves the graph's ``GraphPlan``
(PNG compress + transpose, partitioning, gather schedules — paper
§IV-B) through the process-level plan cache, runs 20 PageRank
iterations, checks the engines agree, and prints the paper's headline
statistics: compression ratio r, modeled bytes per edge (eqs. 3-5)
and measured per-iteration time.  Plans of one (graph, part_size)
share one PNG build, and re-opening a session costs zero preprocessing.

``--serve`` continues into the serving layer: ``sess.serve()`` hands
back a continuous-batching SlotScheduler (DESIGN.md §7) answering
mixed queries — personalized seeds, per-request tolerances, on-device
top-k — from the SAME plan.  The full multi-graph demo is
examples/serve_pagerank.py.

``--stream`` demos the dynamic-graph subsystem (DESIGN.md §9): edge
batches stream into the session, each one patching the plan's dirty
partitions in place of a full rebuild, and ``pagerank(warm=True)``
repairs the previous ranks with a residual push seeded at the changed
edges instead of re-iterating from scratch.

``--ingest`` demos the real-graph pipeline (DESIGN.md §12) on the
bundled SNAP-style fixture: streaming parse, external->internal id
mapping, offsite-link filtering with virtual-mass accounting,
locality relabeling (``reorder="hybrid"``), and results — top-10,
personalized serve — reported in the FILE's original ids.

Migration note (pre-Session API): the old entry points still work —

    eng = SpMVEngine(g, method="pcpm", part_size=p)   # old
    res = pagerank(g, engine=eng, num_iterations=20)
    srv = PageRankServer(g, method="pcpm", ...)
    sch = SlotScheduler(g, method="pcpm", ...)

is now spelled

    sess = repro.open(g, repro.EngineConfig(method="pcpm",
                                            part_size=p))
    res  = sess.pagerank(num_iterations=20)
    srv  = sess.server(...)
    sch  = sess.serve(...)

The old constructors are thin shims over the same plan cache and
backend registry, so both forms share plans and stay in lockstep;
prefer the Session form — one EngineConfig instead of four keyword
sets, and every workload amortizes one preprocessing pass.
"""
import argparse
import time

import numpy as np

import repro
from repro.core.comm_model import (ModelParams, pdpr_bytes, bvgas_bytes,
                                   pcpm_bytes)
from repro.core.pagerank import pagerank_reference
from repro.graphs import generators


def ingest_demo():
    """Real-graph ingest (DESIGN.md §12) end to end on the committed
    SNAP-style fixture — the path a crawl dump takes into a served
    session, with every id the caller sees in the FILE's labels."""
    import tempfile
    from pathlib import Path

    from repro.ingest import LinkFilter, NodeIdMapping, ingest_edge_list

    fixture = (Path(__file__).resolve().parent.parent
               / "tests" / "fixtures" / "web_sample.txt")
    res = ingest_edge_list(
        fixture,
        filters=[LinkFilter("offsite", lambda s, d: d < 900_000_000)],
        self_loops="drop", dedup=True)
    print(f"ingest: {res.stats.summary()}")

    # hybrid relabeling for locality; results map back transparently
    sess = res.open(part_size=16, num_iterations=60, tol=0.0,
                    reorder="hybrid", slots=2, chunk=4)
    out = sess.pagerank()
    print(f"solved {res.graph.num_nodes} nodes in {out.iterations} "
          f"iterations (plan r={sess.plan.compression_ratio:.2f}, "
          f"reorder={sess.config.reorder})")
    ids, scores = sess.top_ranked(10)
    print("top-10 (external ids):")
    for i, s in zip(ids.tolist(), scores.tolist()):
        print(f"  {i:>9d}  {s:.5f}")

    # mass that would have flowed down the filtered offsite links
    for cat, mass in res.virtual_mass(out.ranks).items():
        print(f"virtual mass [{cat}]: {mass:.4f} "
              f"({res.virtual.counts[cat]} links)")

    # personalized serve query, seeded AND answered by external id
    ext_seed = int(ids[0])
    seeds = np.zeros(res.graph.num_nodes, np.float32)
    seeds[res.idmap.to_internal(np.int64(ext_seed))] = 1.0
    sch = sess.serve()
    sch.submit(seeds, top_k=5, tol=1e-5, max_iters=100)
    sch.run_until_drained()
    (q,) = sch.completed
    print(f"personalized from {ext_seed}: top-5 external "
          f"{q.top_external.tolist()} ({q.iterations} iters)")

    # persist plan + id map side by side: a restarted server reloads
    # both and serves external ids with zero preprocessing
    with tempfile.TemporaryDirectory() as td:
        plan_p, map_p = f"{td}/web.plan.npz", f"{td}/web.idmap.npz"
        sess.plan.save(plan_p)
        res.idmap.save(map_p)
        m2 = NodeIdMapping.load(map_p)
        assert (m2.external_ids == res.idmap.external_ids).all()
        print(f"persisted plan + id map "
              f"({m2.num_nodes} external ids round-tripped)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=15)
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--serve", action="store_true",
                    help="also demo the continuous-batching query "
                         "scheduler (examples/serve_pagerank.py has "
                         "the full version)")
    ap.add_argument("--stream", action="store_true",
                    help="also demo streaming edge deltas: "
                         "incremental plan patching + residual-push "
                         "warm rank updates (DESIGN.md §9)")
    ap.add_argument("--ingest", action="store_true",
                    help="demo the real-graph ingest pipeline on the "
                         "bundled fixture: parse -> id map -> filter "
                         "-> reorder -> solve/serve in external ids "
                         "(DESIGN.md §12)")
    args = ap.parse_args()

    if args.ingest:
        return ingest_demo()

    g = generators.rmat(args.scale, args.edge_factor, seed=7)
    part_size = max(256, g.num_nodes // 64)
    print(f"kron graph: n={g.num_nodes:,} m={g.num_edges:,} "
          f"part_size={part_size}")

    results = {}
    for method in ("pdpr", "bvgas", "pcpm"):
        sess = repro.open(g, repro.EngineConfig(
            method=method, part_size=part_size,
            num_iterations=args.iters))
        t0 = time.perf_counter()
        res = sess.pagerank()
        res.ranks.block_until_ready()
        dt = (time.perf_counter() - t0) / args.iters
        results[method] = np.asarray(res.ranks)
        gteps = g.num_edges / dt / 1e9
        extra = (f"  r={sess.plan.compression_ratio:.2f}"
                 if method == "pcpm" else "")
        print(f"{method:6s}: {dt * 1e3:7.1f} ms/iter "
              f"({gteps:.3f} GTEPS){extra}")

    # engines agree with each other and with the dense oracle
    for m in ("bvgas", "pcpm"):
        np.testing.assert_allclose(results[m], results["pdpr"],
                                   rtol=1e-4, atol=1e-9)
    if g.num_nodes <= 1 << 15:
        ref = pagerank_reference(g, num_iterations=args.iters)
        np.testing.assert_allclose(results["pcpm"], ref, rtol=1e-3,
                                   atol=1e-7)
    print("engines agree ✓")

    # re-opening is free: the plan cache already holds this config
    sess = repro.open(g, repro.EngineConfig(method="pcpm",
                                            part_size=part_size))
    stats = repro.plan_cache_stats()
    print(f"plan cache: {stats.plan_builds} builds, "
          f"{stats.plan_hits} hits (reopen cost zero preprocessing)")
    pm = ModelParams(g.num_nodes, g.num_edges,
                     sess.plan.partitioning.num_partitions,
                     sess.plan.compression_ratio)
    print(f"modeled bytes/edge  pdpr(worst)={pdpr_bytes(pm)/g.num_edges:.1f}"
          f"  bvgas={bvgas_bytes(pm)/g.num_edges:.1f}"
          f"  pcpm={pcpm_bytes(pm)/g.num_edges:.1f}")

    if args.serve:
        sch = sess.serve(slots=4, chunk=4)     # shares the session plan
        sch.submit(tol=0.0, max_iters=args.iters)          # uniform
        seeds = np.zeros(g.num_nodes, np.float32)
        seeds[0] = 1.0
        sch.submit(seeds, tol=1e-5, max_iters=100)         # personalized
        sch.submit(top_k=10, tol=1e-4, max_iters=100)      # top-k only
        for r in sch.run_until_drained():
            what = (f"top10 ids {r.top_ids[:4]}..."
                    if r.top_ids is not None else "full ranks")
            print(f"serve: uid={r.uid} it={r.iterations} "
                  f"conv={r.converged} {what}")
        s = sch.metrics.summary()
        print(f"serve: {s['qps']:.1f} qps, p50={s['p50_ms']:.1f}ms "
              f"(see examples/serve_pagerank.py)")

    if args.stream:
        import time as _t
        rng = np.random.default_rng(1)
        n, m = sess.graph.num_nodes, sess.graph.num_edges
        base = sess.pagerank(tol=1e-6, num_iterations=300)
        print(f"\nstream: solved cold in {base.iterations} iterations;"
              " now inserting edge batches...")
        for batch in range(3):
            # new content arrives clustered: this batch's edges land
            # in two destination partitions, so the plan patch splices
            # 2/64 partitions and leaves the rest untouched
            k = m // 1000
            band = np.flatnonzero(sess.graph.dst
                                  < 2 * part_size).astype(np.int64)
            ridx = rng.choice(band, size=k, replace=False)
            delta = repro.GraphDelta.of(
                add=np.stack([rng.integers(0, n, k),
                              rng.integers(0, 2 * part_size, k)],
                             axis=1),
                remove=np.stack([sess.graph.src[ridx],
                                 sess.graph.dst[ridx]], axis=1))
            patches0 = repro.plan_cache_stats().plan_patches
            t0 = _t.perf_counter()
            sess.apply_delta(delta)
            res = sess.pagerank(warm=True, tol=1e-6,
                                num_iterations=300)
            res.ranks.block_until_ready()
            dt = _t.perf_counter() - t0
            patched = repro.plan_cache_stats().plan_patches > patches0
            print(f"stream: batch {batch}: ±{k} edges -> plan "
                  f"{'patched' if patched else 'rebuilt'}, "
                  f"{res.iterations} push sweeps, warm update "
                  f"{dt * 1e3:.0f} ms (vs {base.iterations}-iteration "
                  "cold solve)")


if __name__ == "__main__":
    main()
