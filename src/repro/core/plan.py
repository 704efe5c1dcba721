"""GraphPlan — the immutable preprocessing artifact (DESIGN.md §8).

The paper's central amortization argument (§VI-D3) is that PCPM is a
*preprocess-then-iterate* method: the PNG layout, partitioning and
gather schedules are built once on the host and reused by every
subsequent SpMV.  This module makes that artifact a first-class value:

- ``PlanConfig``: the hashable knob set that determines a plan
  (method, part_size, num_shards, gather_block) — one config type
  instead of four constructors' keyword soup.
- ``GraphPlan``: everything host-side preprocessing produces for one
  ``(graph, PlanConfig)`` — ``Partitioning``, ``PNGLayout``, blocked /
  gather-schedule variants, sharded layouts.  Immutable and hashable
  (identity), with a non-serialized device-side cache (``_device``)
  where backends park uploaded streams, packed kernels, meshes and
  jitted closures.
- a process-level plan cache keyed on ``(graph fingerprint, config)``
  — every consumer (``SpMVEngine``, ``pagerank()``, ``PageRankServer``,
  ``SlotScheduler``, ``Session``) resolves plans through it, so one
  graph served four ways still sorts its edges exactly once.
- ``save``/``load`` to ``.npz`` so million-node plans load warm
  instead of re-sorting edges (what ``GraphRegistry`` warm-loading
  stores).

The per-backend *build* functions live in ``core/backends.py``; this
module only owns the artifact, the cache and the serialization.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
import weakref
from typing import Any, Optional

import numpy as np

from ..graphs.formats import Graph
from ..obs.trace import phase
from .partition import Partitioning
from .png import BlockedPNG, GatherSchedule, PNGLayout, build_png


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
DEFAULT_GATHER_BLOCK = 256
DEFAULT_PART_SIZE = 65536     # 256 KB of 4-byte values (paper §VI-C)


@dataclasses.dataclass(frozen=True)
class PlanConfig:
    """Host-preprocessing knobs.  Hashable — the cache key half."""
    method: str = "pcpm"
    # None: the backend derives it (DEFAULT_PART_SIZE, or for
    # pcpm_pallas the largest size whose kernel fits VMEM)
    part_size: Optional[int] = None
    num_shards: Optional[int] = None   # sharded backends; None = all devices
    shard_axis: str = "shards"
    gather_block: int = DEFAULT_GATHER_BLOCK
    # locality-enhancing node relabeling (paper §VI-D1, graphs/
    # reorder.py): the plan's layouts are built on the RELABELED graph
    # while the plan itself stays keyed to the original graph's
    # fingerprint — the reorder name is part of this cache-key half,
    # so each ordering gets its own plan/chain entry
    reorder: str = "none"

    def replace(self, **kw) -> "PlanConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)   # eq=False: identity hash
class GraphPlan:
    """Everything host-side preprocessing produced for one
    ``(graph, PlanConfig)``.  Only the fields the plan's backend needs
    are populated; the rest stay None.

    ``_device`` is a runtime-only cache (device uploads, packed kernel
    layouts, meshes, jitted spmv closures, the fused-loop cache) — it
    never serializes and never participates in plan identity.
    """
    config: PlanConfig
    num_nodes: int
    num_edges: int
    partitioning: Partitioning
    # pdpr: edges in pull (dst-sorted) order
    csc_src: Optional[np.ndarray] = None
    csc_dst: Optional[np.ndarray] = None
    # bvgas: edges in dst-partition-major order
    bv_src: Optional[np.ndarray] = None
    bv_dst: Optional[np.ndarray] = None
    # pcpm / pcpm_pallas
    png: Optional[PNGLayout] = None
    schedule: Optional[GatherSchedule] = None
    blocked: Optional[BlockedPNG] = None
    # pcpm_sharded (core/distributed.py ShardedPNG; typed loosely to
    # keep this module importable without the distributed stack)
    sharded: Optional[Any] = None
    # content hash of the graph this plan was built from — lets
    # install_plan refuse a plan/graph mismatch instead of silently
    # serving wrong preprocessing
    graph_fp: Optional[str] = None
    # fingerprint of the graph this plan was PATCHED from (stream/
    # patch.py): patched plans form a parent chain g0 -> g1 -> ... that
    # ``evict_plans`` can release as one unit
    parent_fp: Optional[str] = None
    # locality relabeling (config.reorder != "none"): the layouts above
    # were built on ``g.relabel(reorder_perm)``; every consumer maps
    # inputs in via the inverse and results back via the permutation
    # (``internal_graph`` / ``reorder_inverse`` below)
    reorder_perm: Optional[np.ndarray] = None    # (n,) int32, old -> new
    _device: dict = dataclasses.field(default_factory=dict, repr=False)

    # ------------------------------------------------------------- views
    @property
    def method(self) -> str:
        return self.config.method

    @property
    def part_size(self) -> int:
        return self.config.part_size

    @property
    def num_shards(self) -> Optional[int]:
        return self.config.num_shards

    @property
    def compression_ratio(self) -> float:
        """r = |E| / |E'| — on the wire for sharded plans (paper
        table V / DESIGN.md §6), in DRAM traffic otherwise."""
        if self.sharded is not None:
            return self.sharded.wire_compression
        if self.png is not None:
            return self.png.compression_ratio
        return 1.0

    # ----------------------------------------------------- serialization
    def save(self, path: str) -> None:
        """Persist the host-side artifact as one compressed ``.npz``.

        Device-side state (``_device``) is rebuilt on first use after
        ``load`` — meshes and compiled closures are runtime-specific.
        """
        arrays: dict[str, np.ndarray] = {}
        if self.reorder_perm is not None:
            arrays["reorder_perm"] = self.reorder_perm
        meta: dict[str, Any] = {
            "version": 3,
            "config": dataclasses.asdict(self.config),
            "num_nodes": self.num_nodes,
            "num_edges": self.num_edges,
            "graph_fp": self.graph_fp,
            "parent_fp": self.parent_fp,
        }
        for key in ("csc_src", "csc_dst", "bv_src", "bv_dst"):
            arr = getattr(self, key)
            if arr is not None:
                arrays[key] = arr
        if self.png is not None:
            p = self.png
            arrays.update({"png/update_src": p.update_src,
                           "png/update_offsets": p.update_offsets,
                           "png/edge_update_idx": p.edge_update_idx,
                           "png/edge_dst": p.edge_dst,
                           "png/edge_offsets": p.edge_offsets})
        if self.schedule is not None:
            s = self.schedule
            meta["schedule"] = {"block": s.block, "num_edges": s.num_edges,
                                "window_rows": s.window_rows}
            arrays.update({"sched/eui": s.edge_update_idx_padded,
                           "sched/piece_start": s.piece_start,
                           "sched/piece_end": s.piece_end,
                           "sched/piece_dst": s.piece_dst})
            if s.window_start is not None:
                arrays["sched/window_start"] = s.window_start
        if self.blocked is not None:
            b = self.blocked
            meta["blocked"] = {"part_size": b.part_size,
                               "update_pad_frac": b.update_pad_frac,
                               "edge_pad_frac": b.edge_pad_frac}
            arrays.update({"blk/update_src": b.update_src,
                           "blk/edge_update_local": b.edge_update_local,
                           "blk/edge_dst_local": b.edge_dst_local})
        if self.sharded is not None:
            h = self.sharded
            meta["sharded"] = {"num_shards": h.num_shards,
                               "shard_size": h.shard_size,
                               "num_nodes": h.num_nodes,
                               "gather_block": h.gather_block,
                               "wire_updates": h.wire_updates,
                               "wire_edges": h.wire_edges}
            arrays.update({"shd/send_ids": h.send_ids,
                           "shd/edge_upd": h.edge_upd,
                           "shd/edge_dst": h.edge_dst,
                           "shd/eui_padded": h.eui_padded,
                           "shd/piece_start": h.piece_start,
                           "shd/piece_end": h.piece_end,
                           "shd/piece_dst": h.piece_dst})
        np.savez_compressed(path, __meta__=json.dumps(meta), **arrays)

    @staticmethod
    def load(path: str) -> "GraphPlan":
        z = np.load(path, allow_pickle=False)
        if "__meta__" not in z:
            raise ValueError(
                f"{path!r} is not a GraphPlan file (no __meta__ entry "
                "— a raw graph npz goes through graphs.io.load)")
        meta = json.loads(str(z["__meta__"]))
        if meta.get("version") not in (1, 2, 3):
            raise ValueError(
                f"unsupported plan format version {meta.get('version')!r}"
                f" in {path!r} (this build reads versions 1-3)")
        # pre-v3 configs lack the reorder key; the dataclass default
        # ("none") is exactly what those plans were built with
        cfg = PlanConfig(**meta["config"])
        n, m = int(meta["num_nodes"]), int(meta["num_edges"])
        part = Partitioning(n, cfg.part_size)
        kw: dict[str, Any] = {}
        if "reorder_perm" in z:
            kw["reorder_perm"] = z["reorder_perm"]
        for key in ("csc_src", "csc_dst", "bv_src", "bv_dst"):
            if key in z:
                kw[key] = z[key]
        if "png/update_src" in z:
            kw["png"] = PNGLayout(part, z["png/update_src"],
                                  z["png/update_offsets"],
                                  z["png/edge_update_idx"],
                                  z["png/edge_dst"],
                                  z["png/edge_offsets"], n, m)
        if "schedule" in meta:
            s = meta["schedule"]
            kw["schedule"] = GatherSchedule(
                int(s["block"]), int(s["num_edges"]), z["sched/eui"],
                z["sched/piece_start"], z["sched/piece_end"],
                z["sched/piece_dst"],
                z["sched/window_start"] if "sched/window_start" in z
                else None, int(s.get("window_rows", 0)))
        if "blocked" in meta:
            b = meta["blocked"]
            kw["blocked"] = BlockedPNG(
                int(b["part_size"]), z["blk/update_src"],
                z["blk/edge_update_local"], z["blk/edge_dst_local"],
                float(b["update_pad_frac"]), float(b["edge_pad_frac"]))
        if "sharded" in meta:
            from .distributed import ShardedPNG
            h = meta["sharded"]
            kw["sharded"] = ShardedPNG(
                int(h["num_shards"]), int(h["shard_size"]),
                int(h["num_nodes"]), z["shd/send_ids"],
                z["shd/edge_upd"], z["shd/edge_dst"],
                int(h["gather_block"]), z["shd/eui_padded"],
                z["shd/piece_start"], z["shd/piece_end"],
                z["shd/piece_dst"], int(h["wire_updates"]),
                int(h["wire_edges"]))
        graph_fp = meta.get("graph_fp")
        if meta["version"] < 2:
            # v1 fingerprints are sha1-of-sorted-edges; current builds
            # use the multiset hash — drop the stale fp (install_plan
            # re-stamps it) rather than spuriously reject the plan
            graph_fp = None
        if "schedule" not in kw and cfg.method in ("pdpr", "bvgas"):
            # version-1 files predate the baseline engines adopting the
            # blocked gather; the schedule is a sort-free O(M) derive
            # (pdpr) / one argsort (bvgas) from the stored streams
            from .backends import bvgas_schedule, pdpr_schedule
            if cfg.method == "pdpr":
                kw["schedule"] = pdpr_schedule(
                    kw["csc_src"], kw["csc_dst"], num_nodes=n,
                    block=cfg.gather_block)
            else:
                kw["schedule"] = bvgas_schedule(
                    kw["bv_dst"], num_nodes=n, block=cfg.gather_block)
        if cfg.reorder != "none" and "reorder_perm" not in kw:
            raise ValueError(
                f"{path!r} declares reorder={cfg.reorder!r} but stores "
                "no permutation — refusing to serve internal-space "
                "layouts without the mapping back")
        return GraphPlan(cfg, n, m, part, graph_fp=graph_fp,
                         parent_fp=meta.get("parent_fp"), **kw)


# ---------------------------------------------------------------------------
# Process-level plan cache
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class PlanCacheStats:
    plan_builds: int = 0
    plan_hits: int = 0
    png_builds: int = 0
    png_hits: int = 0
    plan_patches: int = 0    # incremental patches (stream/patch.py)
    # cumulative host seconds of each build phase (``build_phase``)
    check_s: float = 0.0            # validate + fingerprint
    reorder_s: float = 0.0          # locality relabeling (graphs/reorder.py)
    png_build_s: float = 0.0        # core/png.py build_png
    schedule_build_s: float = 0.0   # gather schedules
    upload_s: float = 0.0           # streams to the device


_PLAN_CACHE: dict[tuple, GraphPlan] = {}
_PNG_CACHE: dict[tuple, PNGLayout] = {}
_STATS = PlanCacheStats()

# Observability taps (obs/__init__.py Observability registers itself).
# WeakSet: a dropped Observability stops receiving events without an
# unregister call; emission with no observers is one falsy check.
_PLAN_OBSERVERS: "weakref.WeakSet" = weakref.WeakSet()


def add_plan_observer(obs) -> None:
    """Register an object with a ``plan_event(name, **attrs)`` method
    to receive plan build/hit/patch notifications (held weakly)."""
    _PLAN_OBSERVERS.add(obs)


def remove_plan_observer(obs) -> None:
    _PLAN_OBSERVERS.discard(obs)


def notify_plan_event(name: str, **attrs) -> None:
    """Fan an event out to registered observers.  Observer errors are
    swallowed: telemetry must never fail a build."""
    if not _PLAN_OBSERVERS:
        return
    for obs in list(_PLAN_OBSERVERS):
        try:
            obs.plan_event(name, **attrs)
        except Exception:
            pass

# Bound on cached entries: a long-lived process streaming many graphs
# through the (shim) constructors must not pin preprocessing arrays +
# device uploads without limit.  Overflow evicts the oldest entry —
# safe, because live engines/Sessions hold their own plan reference;
# only a future cache hit is lost.  ``evict_plans(g)`` retires a
# specific graph eagerly.
MAX_CACHED_PLANS = 128
MAX_CACHED_PNGS = 128


def _bounded_insert(cache: dict, limit: int, key, value) -> None:
    if key not in cache and len(cache) >= limit:
        cache.pop(next(iter(cache)))       # least recently used
    cache[key] = value


def _touch(cache: dict, key) -> None:
    """Refresh recency (dicts iterate in insertion order, so a hit
    moves the entry to the back — a hot graph's plan is never the
    one evicted by a stream of one-shot graphs)."""
    cache[key] = cache.pop(key)


def plan_cache_stats() -> PlanCacheStats:
    """Live build/hit counters (tests assert build count == 1) and the
    cumulative seconds of each build phase."""
    return _STATS


def clear_plan_cache() -> None:
    """Drop every cached plan and PNG layout and reset the counters."""
    _PLAN_CACHE.clear()
    _PNG_CACHE.clear()
    for f in dataclasses.fields(_STATS):
        setattr(_STATS, f.name, f.default)


# build phase -> (profiler span, PlanCacheStats field it adds to)
_BUILD_PHASES = {"reorder": ("repro.plan.reorder", "reorder_s"),
                 "png": ("repro.plan.png", "png_build_s"),
                 "schedule": ("repro.plan.schedule", "schedule_build_s"),
                 "upload": ("repro.plan.upload", "upload_s")}


@contextlib.contextmanager
def build_phase(name: str):
    """One phase of a plan build (``reorder``, ``png``, ``schedule``,
    ``upload``): a ``repro.plan.<name>`` profiler span, its host
    seconds added to the phase's ``PlanCacheStats`` field.
    (``repro.plan.check`` is ``build_plan``'s own: it counts only when
    the cache misses.)"""
    span, field = _BUILD_PHASES[name]
    t0 = time.perf_counter()
    try:
        with phase(span):
            yield
    finally:
        setattr(_STATS, field,
                getattr(_STATS, field) + time.perf_counter() - t0)


def peek_plan(fp: str, config: PlanConfig) -> Optional[GraphPlan]:
    """Plan-cache lookup by fingerprint without building on miss (the
    hit refreshes LRU recency and counts as a cache hit) — the public
    seam the incremental patcher uses, so the cache's key/LRU/stats
    policy stays in this module."""
    plan = _PLAN_CACHE.get((fp, config))
    if plan is not None:
        _STATS.plan_hits += 1
        _touch(_PLAN_CACHE, (fp, config))
    return plan


def peek_shared_png(fp: str, part_size: int) -> Optional[PNGLayout]:
    """PNG-cache lookup by fingerprint without building on miss — the
    incremental patcher (stream/patch.py) uses it so a pcpm patch and
    a pcpm_pallas patch of the same delta share ONE spliced layout."""
    png = _PNG_CACHE.get((fp, part_size))
    if png is not None:
        _STATS.png_hits += 1
        _touch(_PNG_CACHE, (fp, part_size))
    return png


def _edge_hash64(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """splitmix64 of the packed (src, dst) pair, vectorized (uint64
    arithmetic wraps, which is the point)."""
    h = ((src.astype(np.uint64) << np.uint64(32))
         | dst.astype(np.uint64))
    h = (h ^ (h >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    h = (h ^ (h >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return h ^ (h >> np.uint64(31))


def _fp_string(num_nodes: int, num_edges: int, parts) -> str:
    return (f"{num_nodes:x}.{num_edges:x}."
            f"{int(parts[0]):016x}{int(parts[1]):016x}")


def graph_fingerprint(g: Graph) -> str:
    """Content hash of the edge MULTISET — two equal graphs share
    plans even when their COO edge lists arrive in different orders
    (every backend lexsorts before building, so the plans are
    identical).

    The hash is a commutative-invertible pair (sum, xor) over per-edge
    splitmix64 values: order-independent WITHOUT sorting (one O(M)
    vectorized pass, vs. the lexsort a content sort would cost), and
    incrementally updatable — ``stream.apply_delta`` derives the new
    graph's fingerprint from the old one in O(|delta|), so a delta
    stream never re-hashes the full edge list.  Memoized on the
    instance."""
    fp = g.__dict__.get("_plan_fingerprint")
    if fp is None:
        parts = g.__dict__.get("_fp_parts")
        if parts is None:
            h = _edge_hash64(g.src, g.dst)
            parts = (int(h.sum(dtype=np.uint64)),
                     int(np.bitwise_xor.reduce(h, initial=np.uint64(0))))
            g.__dict__["_fp_parts"] = parts   # frozen-safe: dict write
        fp = _fp_string(g.num_nodes, g.num_edges, parts)
        g.__dict__["_plan_fingerprint"] = fp
    return fp


def validate_plan(g: Graph, plan: GraphPlan) -> GraphPlan:
    """Raise ``ValueError`` unless ``plan`` belongs to ``g`` (size and
    content fingerprint) — shared guard of ``install_plan`` and
    ``SpMVEngine(plan=...)``; a wrong plan must fail loudly, never
    silently serve wrong preprocessing."""
    if (plan.num_nodes, plan.num_edges) != (g.num_nodes, g.num_edges):
        raise ValueError(
            f"plan/graph mismatch: plan is for n={plan.num_nodes}, "
            f"m={plan.num_edges}; graph has n={g.num_nodes}, "
            f"m={g.num_edges}")
    fp = graph_fingerprint(g)
    if plan.graph_fp is not None and plan.graph_fp != fp:
        raise ValueError(
            "plan/graph mismatch: the plan was built from a graph "
            "with a different edge set (content fingerprint "
            f"{plan.graph_fp[:12]}… != {fp[:12]}…)")
    return plan


def shared_png(g: Graph, part_size: int) -> PNGLayout:
    """The PNG layout for ``(graph, part_size)`` — method-independent,
    so ``pcpm`` and ``pcpm_pallas`` plans at the same part size share
    ONE build.  Their default part sizes differ (``pcpm_pallas`` derives
    its own from VMEM), so they share only when both are given the same
    explicit size that the kernel can fit."""
    key = (graph_fingerprint(g), part_size)
    png = _PNG_CACHE.get(key)
    if png is not None:
        _STATS.png_hits += 1
        _touch(_PNG_CACHE, key)
        return png
    _STATS.png_builds += 1
    t0 = time.perf_counter()
    with build_phase("png"):
        png = build_png(g, Partitioning(g.num_nodes, part_size))
    _bounded_insert(_PNG_CACHE, MAX_CACHED_PNGS, key, png)
    notify_plan_event("png_build", part_size=part_size,
                      n=g.num_nodes, m=g.num_edges,
                      duration_s=time.perf_counter() - t0)
    return png


def build_plan(g: Graph, config: PlanConfig | None = None) -> GraphPlan:
    """THE way to get a plan: normalize the config, consult the
    process-level cache, delegate a miss to the registered backend's
    ``build_plan``."""
    from .backends import get_backend, normalize_config
    from ..graphs.formats import validate_graph
    t0 = time.perf_counter()
    with phase("repro.plan.check"):
        validate_graph(g)     # crisp ValueError on out-of-range ids,
        cfg = normalize_config(g, config or PlanConfig())  # not a crash
        fp = graph_fingerprint(g)
    key = (fp, cfg)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        _STATS.plan_hits += 1
        _touch(_PLAN_CACHE, key)
        notify_plan_event("plan_cache_hit", method=cfg.method,
                          fp=fp[:12])
        return plan
    _STATS.plan_builds += 1
    _STATS.check_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    if cfg.reorder != "none":
        # build every layout on the RELABELED graph (that's the whole
        # point — contiguous hub labels raise PNG compression), but
        # stamp the ORIGINAL graph's fingerprint: the plan belongs to
        # g, and the reorder name in cfg keeps the cache entry distinct;
        # the relabeled graph is kept as the plan's internal graph
        from ..graphs.reorder import reorder_permutation
        with build_phase("reorder"):
            perm = reorder_permutation(g, cfg.reorder)
            gi = g.relabel(perm)
        plan = get_backend(cfg.method).build_plan(gi, cfg)
        plan = dataclasses.replace(plan, reorder_perm=perm, graph_fp=fp)
        plan._device["internal_graph"] = gi
    else:
        plan = get_backend(cfg.method).build_plan(g, cfg)
    if plan.graph_fp is None:
        plan = dataclasses.replace(plan, graph_fp=fp)
    _bounded_insert(_PLAN_CACHE, MAX_CACHED_PLANS, key, plan)
    notify_plan_event("plan_build", method=cfg.method,
                      n=g.num_nodes, m=g.num_edges,
                      reorder=cfg.reorder, fp=fp[:12],
                      duration_s=time.perf_counter() - t0)
    return plan


def install_plan(g: Graph, plan: GraphPlan) -> GraphPlan:
    """Seed the cache with a plan built elsewhere (e.g. ``GraphPlan.
    load`` of a persisted million-node plan) so every subsequent
    ``build_plan``/``Session``/scheduler on ``g`` with the same config
    warm-starts instead of re-sorting edges.

    Raises ``ValueError`` when the plan does not belong to ``g`` (size
    or content-fingerprint mismatch, see ``validate_plan``) — a wrong
    plan would otherwise silently serve wrong preprocessing."""
    from .backends import normalize_config
    validate_plan(g, plan)
    fp = graph_fingerprint(g)
    cfg = normalize_config(g, plan.config)
    if plan.graph_fp is None:
        plan = dataclasses.replace(plan, graph_fp=fp)
    _bounded_insert(_PLAN_CACHE, MAX_CACHED_PLANS, (fp, cfg), plan)
    # a reordered plan's PNG is of the RELABELED graph — seeding the
    # shared PNG cache under the original fingerprint would poison a
    # later reorder="none" build of the same (graph, part_size)
    if (plan.png is not None and plan.reorder_perm is None
            and (fp, cfg.part_size) not in _PNG_CACHE):
        _bounded_insert(_PNG_CACHE, MAX_CACHED_PNGS,
                        (fp, cfg.part_size), plan.png)
    return plan


def internal_graph(g: Graph, plan: GraphPlan) -> Graph:
    """The graph the plan's layouts actually index: ``g`` itself for
    plain plans, ``g.relabel(perm)`` (cached on the plan) for reordered
    ones.  Fused drivers, steppers and push engines run wholly in this
    internal space — results map back once at the boundary, so the
    locality win is never taxed by per-iteration permutes."""
    if plan.reorder_perm is None:
        return g
    gi = plan._device.get("internal_graph")
    if gi is None:
        gi = g.relabel(plan.reorder_perm)
        plan._device["internal_graph"] = gi
    return gi


def reorder_inverse(plan: GraphPlan) -> np.ndarray:
    """``inv[internal_id] = original_id`` for a reordered plan (cached
    on the plan's runtime dict)."""
    inv = plan._device.get("reorder_inv")
    if inv is None:
        from ..graphs.reorder import inverse_permutation
        inv = inverse_permutation(plan.reorder_perm)
        plan._device["reorder_inv"] = inv
    return inv


def plan_nbytes(plan: GraphPlan) -> int:
    """Footprint of a plan in bytes — the sum of every array ``save``
    would persist, and, where the pcpm window kernel engages, of its
    row stream (one int32 per arc, ``kernels/pcpm_expand.arc_rows``),
    which the upload adds.  This is what a multi-graph
    registry's memory budget accounts against (serve/scheduler.py
    GraphRegistry): the plan streams dominate a resident graph's cost,
    and unlike device buffers they are exactly enumerable."""
    from .backends import window_engages
    arrays: list[np.ndarray] = []
    if plan.reorder_perm is not None:
        arrays.append(plan.reorder_perm)
    for key in ("csc_src", "csc_dst", "bv_src", "bv_dst"):
        arr = getattr(plan, key)
        if arr is not None:
            arrays.append(arr)
    if plan.png is not None:
        p = plan.png
        arrays += [p.update_src, p.update_offsets, p.edge_update_idx,
                   p.edge_dst, p.edge_offsets]
    if plan.schedule is not None:
        s = plan.schedule
        arrays += [s.edge_update_idx_padded, s.piece_start,
                   s.piece_end, s.piece_dst]
        if s.window_start is not None:
            arrays.append(s.window_start)
        if window_engages(s):
            # the row stream: one int32 per arc, as the indices
            arrays.append(s.edge_update_idx_padded)
    if plan.blocked is not None:
        b = plan.blocked
        arrays += [b.update_src, b.edge_update_local, b.edge_dst_local]
    if plan.sharded is not None:
        h = plan.sharded
        arrays += [h.send_ids, h.edge_upd, h.edge_dst, h.eui_padded,
                   h.piece_start, h.piece_end, h.piece_dst]
    return sum(int(np.asarray(a).nbytes) for a in arrays)


def _chain_fingerprints(fp: str) -> set[str]:
    """Every fingerprint connected to ``fp`` through cached plans'
    ``parent_fp`` links (both directions, transitively).  A stream of
    patched plans forms a chain g0 -> g1 -> ... gT; retiring any link
    retires the whole chain — the intermediate graphs are gone, so
    their plans can never be cache-hit again."""
    fps = {fp}
    changed = True
    while changed:
        changed = False
        for plan in _PLAN_CACHE.values():
            links = {f for f in (plan.graph_fp, plan.parent_fp)
                     if f is not None}
            if links & fps and not links <= fps:
                fps |= links
                changed = True
    return fps


def evict_plans(g: Graph, *, chain: bool = True) -> int:
    """Drop every cached plan/PNG for ``g`` (a long-lived server that
    rotates graphs uses this instead of the nuclear
    ``clear_plan_cache``); live Sessions/engines keep their plan
    references, only the cache entries — and with them the pinned
    host + device memory once those references drop — are released.

    ``chain=True`` (default) also releases every plan linked to ``g``
    through ``parent_fp`` patch chains (stream/patch.py): evicting any
    version of a dynamically-updated graph releases all its patched
    ancestors/descendants, so a delta stream cannot pin memory through
    stale intermediate versions.  Returns the number of entries
    evicted."""
    fps = ({graph_fingerprint(g)} if not chain
           else _chain_fingerprints(graph_fingerprint(g)))
    plan_keys = [k for k in _PLAN_CACHE if k[0] in fps]
    png_keys = [k for k in _PNG_CACHE if k[0] in fps]
    for k in plan_keys:
        del _PLAN_CACHE[k]
    for k in png_keys:
        del _PNG_CACHE[k]
    return len(plan_keys) + len(png_keys)
