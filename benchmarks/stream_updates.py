"""Dynamic-graph update benchmark (DESIGN.md §9): after an edge delta,
how fast are warm ranks back?

Two paths race from the same starting state (a solved graph with a
built plan — the steady state of a serving deployment):

- **warm**:  incremental plan patch (dirty partitions only)
             + residual-push rank update seeded at the changed edges;
- **cold**:  full plan rebuild on a fresh graph handle
             + full power iteration.

Both sides pay their own trace/compile and device upload — each row is
wall-clock from "delta arrives" to "updated ranks on device".  Two
regimes per delta:

- ``*20`` — the repo's standard benchmark convention (BENCH e2e rows):
  cold runs the fixed 20 iterations; warm pushes to the SAME stopping
  residual cold achieved, so warm accuracy >= cold accuracy (both
  reported against a deep-converged reference).
- ``*_tol`` — deep convergence: both sides run to an L1 stopping
  residual of 1e-6 (identical stopping rule; the push's per-sweep L1
  change is exactly the fused driver's per-step L1 change).

Deltas are half removals / half insertions.  The *localized* deltas
land in a small band of destination partitions (the new-content
arrival pattern incremental patching is built for); the *scattered*
delta sprays uniformly, dirties every partition, and is reported
anyway — it exercises the full-rebuild fallback, so its patch row
honestly costs ~a rebuild while the push still wins.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.pagerank import pagerank
from repro.core.plan import PlanConfig, build_plan, evict_plans
from repro.core.spmv import SpMVEngine
from repro.graphs.formats import Graph
from repro.stream import GraphDelta, apply_delta, patch_plan, update_ranks
from .common import Csv, Dataset
from .pagerank_e2e import _upload_plan

TOL = 1e-6           # deep-convergence regime stopping residual


def _band_delta(g: Graph, frac: float, part_size: int,
                rng: np.random.Generator, *,
                scattered: bool = False) -> GraphDelta:
    """~frac·m changed edges: half removals, half inserts.  Localized
    deltas confine destinations to a band of partitions just big
    enough to supply the removals."""
    n, m = g.num_nodes, g.num_edges
    half = max(1, int(m * frac) // 2)
    if scattered:
        rem_pool = np.arange(m)
        add_dst = rng.integers(0, n, size=half).astype(np.int32)
    else:
        k = -(-n // part_size)
        band = max(1, int(np.ceil(2.0 * half / (m / k))))
        in_band = g.dst < band * part_size
        rem_pool = np.flatnonzero(in_band)
        half = min(half, len(rem_pool))
        add_dst = rng.integers(0, min(band * part_size, n),
                               size=half).astype(np.int32)
    ridx = rng.choice(rem_pool, size=half, replace=False)
    add = np.stack([rng.integers(0, n, size=half).astype(np.int32),
                    add_dst], axis=1)
    rem = np.stack([g.src[ridx], g.dst[ridx]], axis=1)
    return GraphDelta.of(add=add, remove=rem)


def _linf(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


def _bench_delta(csv: Csv, tag: str, g: Graph, plan0, prev_ranks,
                 delta: GraphDelta, cfg: PlanConfig, *,
                 deep: bool = True) -> None:
    k = plan0.partitioning.num_partitions
    dirty = len(delta.dirty_partitions(plan0.part_size))
    g2 = apply_delta(g, delta)

    # ---- warm: incremental plan patch
    t0 = time.perf_counter()
    p2 = patch_plan(plan0, delta, g2)
    _upload_plan(p2)
    t_patch = time.perf_counter() - t0

    # ---- cold: fresh graph handle, evicted cache, full rebuild
    g2c = Graph(g2.num_nodes, g2.src.copy(), g2.dst.copy())
    evict_plans(g2, chain=False)
    t0 = time.perf_counter()
    p2c = build_plan(g2c, cfg)
    _upload_plan(p2c)
    t_rebuild = time.perf_counter() - t0
    cold_eng = SpMVEngine(g2c, plan=p2c)

    # ---- standard regime: cold runs the fixed 20 iterations, warm
    #      pushes to the residual cold achieved
    t0 = time.perf_counter()
    cold20 = pagerank(g2c, engine=cold_eng, num_iterations=20, tol=0.0)
    cold20.ranks.block_until_ready()
    t_iter20 = time.perf_counter() - t0
    res20 = cold20.residuals[-1]
    t0 = time.perf_counter()
    warm20 = update_ranks(p2, delta, prev_ranks, g_old=g, g_new=g2,
                          tol=res20, max_push=400)
    warm20.ranks.block_until_ready()
    t_push20 = time.perf_counter() - t0

    # deep-converged reference for the accuracy columns (untimed)
    ref = pagerank(g2c, engine=cold_eng, num_iterations=400, tol=1e-8)
    csv.add(f"{tag}/patch", t_patch,
            f"dirty={dirty}/{k},spliced={int(dirty / k <= 0.5)}")
    csv.add(f"{tag}/rebuild", t_rebuild)
    csv.add(f"{tag}/recompute20", t_iter20,
            f"iters=20,res={res20:.1e},err={_linf(cold20.ranks, ref.ranks):.1e}")
    csv.add(f"{tag}/push20", t_push20,
            f"sweeps={warm20.iterations}"
            f",err={_linf(warm20.ranks, ref.ranks):.1e}")
    csv.add(f"{tag}/speedup20", 0.0,
            f"cold_ms={(t_rebuild + t_iter20) * 1e3:.0f}"
            f",warm_ms={(t_patch + t_push20) * 1e3:.0f}"
            f",x={(t_rebuild + t_iter20) / (t_patch + t_push20):.1f}")

    if deep:
        # ---- deep regime: both sides stop at ‖step‖₁ < TOL
        t0 = time.perf_counter()
        cold_t = pagerank(g2c, engine=cold_eng, num_iterations=400,
                          tol=TOL)
        cold_t.ranks.block_until_ready()
        t_iter_t = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm_t = update_ranks(p2, delta, prev_ranks, g_old=g, g_new=g2,
                              tol=TOL, max_push=400)
        warm_t.ranks.block_until_ready()
        t_push_t = time.perf_counter() - t0
        csv.add(f"{tag}/recompute_tol", t_iter_t,
                f"iters={cold_t.iterations}")
        csv.add(f"{tag}/push_tol", t_push_t,
                f"sweeps={warm_t.iterations}"
                f",Linf_vs_cold={_linf(warm_t.ranks, cold_t.ranks):.1e}")
        csv.add(f"{tag}/speedup_tol", 0.0,
                f"cold_ms={(t_rebuild + t_iter_t) * 1e3:.0f}"
                f",warm_ms={(t_patch + t_push_t) * 1e3:.0f}"
                f",x={(t_rebuild + t_iter_t) / (t_patch + t_push_t):.1f}")
    # leave the cache as the warm path expects for the next delta
    evict_plans(g2, chain=False)


def run(datasets: list[Dataset], *, part_size: int = 65536,
        fracs: tuple = (0.001, 0.01), method: str = "pcpm") -> Csv:
    csv = Csv()
    rng = np.random.default_rng(0)
    for ds in datasets:
        g = ds.graph
        cfg = PlanConfig(method=method, part_size=part_size)
        evict_plans(g)
        plan0 = build_plan(g, cfg)
        _upload_plan(plan0)
        # solved steady state: converged ranks + CSR of the solved
        # graph (what the residual seed reads) are warm by definition
        prev = pagerank(g, engine=SpMVEngine(g, plan=plan0),
                        num_iterations=400, tol=TOL / 10)
        prev.ranks.block_until_ready()
        g.csr
        # steady state also includes a compiled push loop: the pcpm
        # push passes its (bucket-padded) streams as arguments, so one
        # executable serves every subsequent delta — warm it with a
        # throwaway 1-edge delta, exactly as a streaming deployment
        # would have long since done.  (The cold side has no analogue:
        # its fused loop closes over each rebuilt plan's constants.)
        wu = GraphDelta.of(
            add=[[int(g.src[0]), int(g.dst[0] + 1) % g.num_nodes]],
            remove=[[int(g.src[0]), int(g.dst[0])]])
        g_wu = apply_delta(g, wu)
        update_ranks(patch_plan(plan0, wu, g_wu), wu, prev.ranks,
                     g_old=g, g_new=g_wu, tol=0.0,
                     max_push=2).ranks.block_until_ready()
        evict_plans(g_wu, chain=False)
        for frac in fracs:
            _bench_delta(csv, f"stream/{ds.name}/f{frac:g}", g, plan0,
                         prev.ranks, _band_delta(g, frac, part_size,
                                                 rng), cfg)
        _bench_delta(csv, f"stream/{ds.name}/scattered{fracs[-1]:g}",
                     g, plan0, prev.ranks,
                     _band_delta(g, fracs[-1], part_size, rng,
                                 scattered=True), cfg, deep=False)
    return csv
