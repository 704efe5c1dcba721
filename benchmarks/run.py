"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale N] [--only name ...]

Emits ``name,us_per_call,derived`` CSV on stdout.  Default scale=16
(65K nodes, 1-2M edges per dataset) finishes on the 1-core CPU box in
minutes; the paper's graphs are ~1000x larger and live in the dry-run /
roofline analysis instead (EXPERIMENTS.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from .common import Csv, suite


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=16)
    ap.add_argument("--part-size", type=int, default=None,
                    help="default: n/64 (paper-regime partition count)")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of: table4 fig8 table5 table6 fig12 "
                         "table7 dist e2e sharded serve serve_push "
                         "serve_gateway stream locality comm")
    ap.add_argument("--reorder", default=None,
                    choices=["none", "degree", "bfs", "hybrid"],
                    help="add the plan-layer locality job, measuring "
                         "this ordering against 'none' (compression "
                         "ratio r + warm per-iter time through "
                         "EngineConfig(reorder=...)); --only locality "
                         "without this flag measures every ordering")
    ap.add_argument("--shards", type=int, default=None, metavar="N",
                    help="enable the sharded fused-loop comparison "
                         "with N shards (clamped to visible devices; "
                         "force host devices via XLA_FLAGS)")
    ap.add_argument("--serve", action="store_true",
                    help="add the continuous-batching serving load "
                         "benchmark (queries/sec + p50/p99 latency "
                         "alongside the per-iteration SpMV rows)")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write the rows as structured JSON "
                         "(perf-trajectory baseline, e.g. "
                         "BENCH_pagerank.json)")
    args = ap.parse_args(argv)
    if args.json:
        # fail fast on an unwritable path without truncating an
        # existing baseline (a crashed run must not destroy it)
        open(args.json, "a").close()

    t0 = time.time()
    datasets = suite(args.scale)
    from .common import default_part_size
    if args.part_size is None:
        args.part_size = default_part_size(1 << args.scale)
    print(f"# suite scale={args.scale} part_size={args.part_size}: "
          + ", ".join(f"{d.name}(n={d.n},m={d.m})" for d in datasets),
          flush=True)
    print("name,us_per_call,derived")

    from . import (table4_runtime, fig8_comm, table5_locality,
                   table6_comm_locality, fig12_partition_sweep,
                   table7_preproc, dist_wire, pagerank_e2e,
                   sharded_loop, serve_load, serve_push,
                   serve_gateway, stream_updates, locality,
                   comm_live)
    jobs = {
        "table4": lambda: table4_runtime.run(
            datasets, part_size=args.part_size),
        "fig8": lambda: fig8_comm.run(datasets,
                                      part_size=args.part_size),
        "table5": lambda: table5_locality.run(
            datasets, part_size=args.part_size),
        "table6": lambda: table6_comm_locality.run(
            datasets[:3], part_size=args.part_size),
        "fig12": lambda: fig12_partition_sweep.run(datasets[:2]),
        "table7": lambda: table7_preproc.run(
            datasets, part_size=args.part_size),
        "dist": lambda: dist_wire.run(datasets),
        "e2e": lambda: pagerank_e2e.run(datasets[:2],
                                        part_size=args.part_size),
        "sharded": lambda: sharded_loop.run(
            datasets[:2], num_shards=args.shards,
            part_size=args.part_size),
        "serve": lambda: serve_load.run(
            datasets[:2], part_size=args.part_size),
        "serve_push": lambda: serve_push.run(
            datasets[:2], part_size=args.part_size),
        "serve_gateway": lambda: serve_gateway.run(
            datasets[:2], part_size=args.part_size),
        "stream": lambda: stream_updates.run(
            datasets[:1], part_size=args.part_size),
        # --reorder X measures just [none, X]; --only locality with no
        # --reorder sweeps every registered ordering
        "locality": lambda: locality.run(
            datasets[:2], part_size=args.part_size,
            orderings=(["none", args.reorder] if args.reorder
                       else None)),
        # plan-counted vs model comm bytes (DESIGN.md §14)
        "comm": lambda: comm_live.run(datasets[:2],
                                      part_size=args.part_size),
    }
    selected = args.only or [j for j in jobs
                             if j not in ("sharded", "serve",
                                          "serve_push", "serve_gateway",
                                          "locality")]
    if args.shards and "sharded" not in selected:
        selected = selected + ["sharded"]
    if args.reorder and "locality" not in selected:
        selected = selected + ["locality"]
    if args.serve:
        selected = selected + [j for j in ("serve", "serve_push",
                                           "serve_gateway")
                               if j not in selected]
    if "sharded" in selected and args.shards is None:
        args.shards = 8          # job default, recorded in the JSON doc
    out = Csv()
    for name in selected:
        print(f"# --- {name} ---", flush=True)
        out.extend(jobs[name]())
    total_s = time.time() - t0
    print(f"# total {total_s:.0f}s, {len(out.rows)} rows", flush=True)
    if args.json:
        doc = {
            "scale": args.scale,
            "part_size": args.part_size,
            "shards": args.shards,
            "only": selected,
            "total_seconds": round(total_s, 1),
            "datasets": [{"name": d.name, "n": d.n, "m": d.m}
                         for d in datasets],
            "rows": [{"name": n, "us_per_call": round(us, 1),
                      "derived": derived}
                     for n, us, derived in out.rows],
        }
        # plan-build vs iterate split (the paper's preprocess-once
        # amortization): aggregated from the e2e */plan and */iterate
        # rows emitted by benchmarks/pagerank_e2e.py.  The fixed-size
        # pallas_smoke rows are excluded — interpret-mode iteration is
        # orders of magnitude slower and would dominate the ratio.
        split_rows = [(n, us) for n, us, _ in out.rows
                      if "pallas_smoke" not in n]
        plan_us = sum(us for n, us in split_rows
                      if n.endswith("/plan"))
        iter_us = sum(us for n, us in split_rows
                      if n.endswith("/iterate"))
        if plan_us or iter_us:
            doc["plan_vs_iterate"] = {
                "plan_build_us": round(plan_us, 1),
                "iterate_us": round(iter_us, 1),
                "plan_frac": round(plan_us / max(plan_us + iter_us, 1e-9),
                                   4),
            }
        # dynamic-graph update split (DESIGN.md §9): per delta size,
        # warm = incremental patch + residual push vs cold = rebuild +
        # full power iteration, from benchmarks/stream_updates.py rows
        stream_tags = sorted({n.rsplit("/", 1)[0] for n, _, _ in out.rows
                              if n.startswith("stream/")
                              and n.endswith("/patch")})
        if stream_tags:
            by_name = {n: us for n, us, _ in out.rows}

            def _entry(tag):
                e = {"delta": tag.split("/", 2)[2],
                     "graph": tag.split("/", 2)[1],
                     "patch_us": round(by_name[f"{tag}/patch"], 1),
                     "rebuild_us": round(by_name[f"{tag}/rebuild"], 1),
                     "push_us": round(by_name[f"{tag}/push20"], 1),
                     "recompute_us": round(
                         by_name[f"{tag}/recompute20"], 1),
                     "speedup": round(
                         (by_name[f"{tag}/rebuild"]
                          + by_name[f"{tag}/recompute20"])
                         / max(by_name[f"{tag}/patch"]
                               + by_name[f"{tag}/push20"], 1e-9), 2)}
                if f"{tag}/push_tol" in by_name:
                    e["speedup_tol"] = round(
                        (by_name[f"{tag}/rebuild"]
                         + by_name[f"{tag}/recompute_tol"])
                        / max(by_name[f"{tag}/patch"]
                              + by_name[f"{tag}/push_tol"], 1e-9), 2)
                return e

            doc["patch_vs_rebuild"] = [_entry(t) for t in stream_tags]
        # plan-layer reordering summary (ISSUE 8): r + warm per-iter
        # per ordering, with the gain over the unreordered plan
        loc = locality.summarize(out.rows)
        if loc:
            doc["locality"] = loc
        comm = comm_live.summarize(out.rows)
        if comm:
            doc["comm"] = comm
        # merge, don't clobber: row FAMILIES (first path component)
        # this run did not regenerate are carried over from the
        # existing baseline, as are their summary sections — so
        # ``--only comm --json BENCH_pagerank.json`` refreshes the
        # comm/ rows without erasing e2e/table4/stream history
        prev = None
        try:
            with open(args.json) as f:
                prev = json.load(f)
        except (OSError, json.JSONDecodeError):
            prev = None
        if prev and prev.get("rows"):
            new_fams = {r["name"].split("/")[0] for r in doc["rows"]}
            kept = [r for r in prev["rows"]
                    if r["name"].split("/")[0] not in new_fams]
            doc["rows"] = kept + doc["rows"]
            doc["only"] = sorted(set(prev.get("only", []))
                                 | set(selected))
            for sect in ("plan_vs_iterate", "patch_vs_rebuild",
                         "locality", "locality_meta", "comm"):
                if sect not in doc and sect in prev:
                    doc[sect] = prev[sect]
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {args.json}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
