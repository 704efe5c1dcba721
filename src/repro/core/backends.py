"""Backend registry — the run-layer half of the plan/run split
(DESIGN.md §8).

Every SpMV engine registers ONE ``Backend`` entry:

- ``build_plan(g, cfg) -> GraphPlan``: the host-side preprocessing
  (edge sorts, PNG build, schedules) for that method;
- ``spmv_fn(plan) -> (x -> A^T x)``: a ``jax.tree_util.Partial``
  whose leaves are the plan's device-resident streams — what the fused
  ``lax.while_loop`` drivers, the chunk steppers and AOT compilation
  consume.  Consumers pass it to their jitted loops as an ARGUMENT:
  a closed-over device array is baked into the program as a constant,
  which at graph500-22 would put ~0.4 GB of streams into the HLO;
- capability flags (``supports_sharding``, ``supports_aot``,
  ``multi_vector``, ``supports_two_phase``) that consumers branch on
  instead of comparing method strings.

``SpMVEngine``, ``pagerank()``, ``PageRankServer`` and
``SlotScheduler`` all resolve backends through this table, so a new
engine plugs in with one ``register_backend`` call and no call-site
edits.  Device-side uploads are cached on ``plan._device`` — shared by
every consumer of the same plan.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..graphs.formats import Graph
from .partition import Partitioning
from .plan import (DEFAULT_PART_SIZE, GraphPlan, PlanConfig, build_phase,
                   shared_png)
from .png import (GatherSchedule, block_png, build_gather_schedule,
                  flat_gather_schedule)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Backend:
    """One SpMV engine: plan builder + runner + capabilities.

    ``phase_fns`` (optional) returns ``(scatter, gather)`` closures
    over the plan's device streams — the seam for paper-faithful
    phase timing (benchmarks/table4_runtime.py) and for the
    ``two_phase=True`` host-barrier execution; backends without it
    reject ``two_phase=True`` at engine construction.
    """
    name: str
    build_plan: Callable[[Graph, PlanConfig], GraphPlan]
    spmv_fn: Callable[[GraphPlan], Callable]
    supports_sharding: bool = False    # runs under shard_map on a mesh
    supports_aot: bool = True          # closure is .lower().compile()-able
    multi_vector: bool = True          # accepts (n, d) as well as (n,)
    uses_gather_block: bool = False    # plan depends on cfg.gather_block
    # the forward-push QUERY backend (serve/push.py) can answer
    # single-seed personalized queries against this backend's plans —
    # single-device only: the push state is one (n,) vector, so the
    # sharded all-to-all layout has nothing to shard
    supports_push_query: bool = False
    phase_fns: Optional[
        Callable[[GraphPlan], tuple[Callable, Callable]]] = None
    # incremental plan patching (stream/patch.py): rebuild only the
    # partitions an edge delta touched and splice them into the old
    # plan.  ``(plan, g_new, delta) -> GraphPlan`` — backends without
    # it fall back to a full rebuild on every delta.
    patch_plan: Optional[
        Callable[[GraphPlan, Graph, "object"], GraphPlan]] = None
    # ``(g, requested) -> part_size``: resolves ``part_size=None`` and
    # refuses a size the backend cannot run
    part_size: Callable[[Graph, Optional[int]], int] = (
        lambda g, requested: requested or DEFAULT_PART_SIZE)

    @property
    def supports_two_phase(self) -> bool:
        return self.phase_fns is not None

    @property
    def supports_incremental(self) -> bool:
        return self.patch_plan is not None


_REGISTRY: dict[str, Backend] = {}


def register_backend(backend: Backend, *, overwrite: bool = False) -> Backend:
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown method {name!r}; registered: "
                         f"{available_backends()}") from None


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def resolve_method(method: str, *, sharded: bool = False) -> str:
    """Map a requested method (+ the ``sharded=True`` convenience flag
    of the serving front-ends) to a registered backend name: when the
    named backend cannot shard, fall back to the registered
    sharding-capable one."""
    backend = get_backend(method)
    if not sharded or backend.supports_sharding:
        return method
    for b in _REGISTRY.values():
        if b.supports_sharding:
            return b.name
    raise ValueError("sharded=True but no registered backend supports "
                     "sharding")


def check_device_count(num_shards: int) -> None:
    """The single home of the shards-vs-devices rule (used by config
    normalization, the engine's loaded-plan path and mesh building)."""
    avail = jax.device_count()
    if num_shards > avail:
        raise ValueError(f"num_shards={num_shards} exceeds the "
                         f"{avail} available devices")


def resolve_engine(g: Graph, *, method: str, sharded: bool,
                   part_size: Optional[int], num_shards: Optional[int],
                   engine=None):
    """Shared engine resolution of the serving front-ends
    (``PageRankServer``, ``SlotScheduler``): construct through the
    registry when no engine is given, otherwise validate the caller's
    engine against the ``sharded=True`` request."""
    from .spmv import SpMVEngine
    if engine is None:
        return SpMVEngine(g, part_size=part_size, num_shards=num_shards,
                          method=resolve_method(method, sharded=sharded))
    if sharded and not engine.backend.supports_sharding:
        raise ValueError(
            "sharded=True requires a sharding-capable engine; got "
            f"method={engine.method!r}")
    return engine


def normalize_config(g: Graph, cfg: PlanConfig) -> PlanConfig:
    """Canonical cache key: resolve ``part_size=None`` through the
    backend and ``num_shards=None`` to the device count for sharding
    backends (validating both), and blank the knobs a backend ignores
    (sharding fields, gather_block) so configs differing only in
    irrelevant knobs share one plan."""
    from .plan import DEFAULT_GATHER_BLOCK
    backend = get_backend(cfg.method)
    if cfg.reorder != "none":
        from ..graphs.reorder import available_orderings
        if cfg.reorder not in available_orderings():
            raise ValueError(
                f"unknown reorder {cfg.reorder!r}; valid: "
                f"{available_orderings()}")
    kw = {}
    part_size = backend.part_size(g, cfg.part_size)
    if part_size != cfg.part_size:
        kw["part_size"] = part_size
    if backend.supports_sharding:
        shards = cfg.num_shards or jax.device_count()
        check_device_count(shards)
        if shards != cfg.num_shards:
            kw["num_shards"] = shards
    elif cfg.num_shards is not None:
        kw["num_shards"] = None
    # the mesh axis NAME never affects host preprocessing (meshes are
    # cached per axis on plan._device) — keep it out of the cache key
    if cfg.shard_axis != "shards":
        kw["shard_axis"] = "shards"
    if (not backend.uses_gather_block
            and cfg.gather_block != DEFAULT_GATHER_BLOCK):
        kw["gather_block"] = DEFAULT_GATHER_BLOCK
    return cfg.replace(**kw) if kw else cfg


def spmv_fn(plan: GraphPlan):
    """The plan's runner closure, built once and cached on the plan —
    every consumer (engine, drivers, steppers, AOT server) of one plan
    shares one closure and one set of device uploads."""
    fn = plan._device.get("spmv")
    if fn is None:
        fn = get_backend(plan.method).spmv_fn(plan)
        plan._device["spmv"] = fn
    return fn


def two_phase_spmv_fn(plan: GraphPlan):
    """The plan's host-barriered scatter/gather closure (backends with
    ``phase_fns`` only), cached like ``spmv_fn``.  The barrier makes
    the bins round-trip through HBM exactly as the paper's bins
    round-trip through DRAM (phase-timing fidelity)."""
    fn = plan._device.get("two_phase_spmv")
    if fn is None:
        backend = get_backend(plan.method)
        if backend.phase_fns is None:
            raise ValueError(f"backend {plan.method!r} does not support "
                             "two_phase execution")
        scatter, gather = backend.phase_fns(plan)

        def fn(x):
            return gather(jax.block_until_ready(scatter(x)))

        plan._device["two_phase_spmv"] = fn
    return fn


def reorder_device(plan: GraphPlan):
    """Device-resident ``(perm, inv)`` int32 arrays for a reordered
    plan (``perm[old] = new``, ``inv[new] = old``), cached on the plan
    — the one-shot boundary maps (``x_int = x[inv]``,
    ``y_orig = y_int[perm]``) gather through these."""
    dev = plan._device.get("reorder_dev")
    if dev is None:
        from .plan import reorder_inverse
        dev = (jnp.asarray(plan.reorder_perm),
               jnp.asarray(reorder_inverse(plan)))
        plan._device["reorder_dev"] = dev
    return dev


def fused_loop_cache(plan: GraphPlan) -> dict:
    """Per-plan cache of jitted iteration loops/steppers (keyed on
    their hyper-parameters) — shared across every engine wrapping the
    same plan so e.g. ``Session.pagerank()`` and a later shim call
    reuse one trace."""
    return plan._device.setdefault("fused_cache", {})


def sharded_mesh(plan: GraphPlan, axis: str | None = None):
    """The 1-D device mesh a sharded plan runs on (built lazily,
    cached per axis name on the plan).  Raises when the plan wants
    more shards than this runtime has devices — e.g. an 8-shard plan
    loaded on a 1-device box — instead of silently truncating the
    mesh against the plan's fixed-shape shard arrays."""
    from jax.sharding import Mesh
    axis = axis or plan.config.shard_axis
    if plan.sharded is None:
        raise ValueError(
            f"backend {plan.method!r} has no sharded layout (mesh is "
            "only meaningful for sharding backends)")
    shards = plan.sharded.num_shards
    check_device_count(shards)
    key = ("mesh", axis)
    mesh = plan._device.get(key)
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()[:shards]), (axis,))
        plan._device[key] = mesh
    return mesh


# ---------------------------------------------------------------------------
# pdpr — pull-direction baseline (paper alg. 1)
# ---------------------------------------------------------------------------
def _plan_fields(g: Graph, cfg: PlanConfig) -> dict:
    return dict(config=cfg, num_nodes=g.num_nodes, num_edges=g.num_edges,
                partitioning=Partitioning(g.num_nodes, cfg.part_size))


def pdpr_schedule(csc_src: np.ndarray, csc_dst: np.ndarray, *,
                  num_nodes: int, block: int) -> GatherSchedule:
    """Blocked-gather schedule over the pull-order edge stream: the
    "update bins" are x itself, so the per-edge pointer stream is just
    the dst-sorted source ids.  Gives pdpr the same hierarchical
    segmented reduction as pcpm (DESIGN.md §3) — the engines now differ
    only in what they stream, not in how they reduce, which is what
    makes the table-4 comparison honest."""
    eui, starts, ends, pdst = flat_gather_schedule(
        csc_src, csc_dst, num_nodes=num_nodes, block=block)
    return GatherSchedule(block, len(csc_dst), eui, starts, ends, pdst)


def _build_pdpr(g: Graph, cfg: PlanConfig) -> GraphPlan:
    order = np.lexsort((g.src, g.dst))
    src, dst = g.src[order], g.dst[order]
    with build_phase("schedule"):
        sched = pdpr_schedule(src, dst, num_nodes=g.num_nodes,
                              block=cfg.gather_block)
    return GraphPlan(csc_src=src, csc_dst=dst, schedule=sched,
                     **_plan_fields(g, cfg))


def _upload(*arrays):
    """The plan's host streams on the device, waited for (so the
    ``upload`` phase holds the transfer, not just its enqueue); a None
    stays None."""
    with build_phase("upload"):
        return jax.block_until_ready(tuple(
            None if a is None else jnp.asarray(a) for a in arrays))


def _sched_device(plan: GraphPlan):
    dev = plan._device.get("sched")
    if dev is None:
        s = plan.schedule
        dev = _upload(s.edge_update_idx_padded, s.piece_start,
                      s.piece_end, s.piece_dst)
        plan._device["sched"] = dev
    return dev


def _gather(eui, ps, pe, pd, x, *, num_nodes: int, block: int):
    from .spmv import pcpm_gather_blocked
    return pcpm_gather_blocked(x, eui, ps, pe, pd, num_nodes=num_nodes,
                               block=block)


def _streams_fn(fn, *streams, **static):
    """``x -> fn(*streams, x, **static)`` as a pytree whose leaves are
    the streams (see the module docstring)."""
    return jax.tree_util.Partial(functools.partial(fn, **static),
                                 *streams)


def _spmv_pdpr(plan: GraphPlan):
    return _streams_fn(_gather, *_sched_device(plan),
                       num_nodes=plan.num_nodes,
                       block=plan.schedule.block)


# ---------------------------------------------------------------------------
# bvgas — Binning w/ Vertex-centric GAS (paper alg. 2)
# ---------------------------------------------------------------------------
def bvgas_schedule(bv_dst: np.ndarray, *, num_nodes: int,
                   block: int) -> GatherSchedule:
    """Blocked-gather schedule over the per-edge bins: the pointer
    stream is the permutation putting the dst-partition-major bins in
    destination order (bins are written in scatter order and read in
    gather order, exactly the paper's bin round-trip)."""
    gorder = np.argsort(bv_dst, kind="stable").astype(np.int32)
    eui, starts, ends, pdst = flat_gather_schedule(
        gorder, bv_dst[gorder], num_nodes=num_nodes, block=block)
    return GatherSchedule(block, len(bv_dst), eui, starts, ends, pdst)


def _build_bvgas(g: Graph, cfg: PlanConfig) -> GraphPlan:
    dstp = g.dst.astype(np.int64) // cfg.part_size
    order = np.lexsort((g.dst, g.src, dstp))
    dst = g.dst[order]
    with build_phase("schedule"):
        sched = bvgas_schedule(dst, num_nodes=g.num_nodes,
                               block=cfg.gather_block)
    return GraphPlan(bv_src=g.src[order], bv_dst=dst, schedule=sched,
                     **_plan_fields(g, cfg))


def _bvgas_device(plan: GraphPlan):
    dev = plan._device.get("bvgas")
    if dev is None:
        (dev,) = _upload(plan.bv_src)
        plan._device["bvgas"] = dev
    return dev


def _bvgas_pass(src, eui, ps, pe, pd, x, *, num_nodes: int, block: int):
    """bvgas: the scatter into per-edge bins, then the blocked gather."""
    from .spmv import bvgas_scatter
    return _gather(eui, ps, pe, pd, bvgas_scatter(src, x),
                   num_nodes=num_nodes, block=block)


def _spmv_bvgas(plan: GraphPlan):
    return _streams_fn(_bvgas_pass, _bvgas_device(plan),
                       *_sched_device(plan), num_nodes=plan.num_nodes,
                       block=plan.schedule.block)


def _phases_bvgas(plan: GraphPlan):
    from .spmv import bvgas_scatter, pcpm_gather_blocked
    src = _bvgas_device(plan)
    eui, ps, pe, pd = _sched_device(plan)
    n, blk = plan.num_nodes, plan.schedule.block
    return (lambda x: bvgas_scatter(src, x),
            lambda bins: pcpm_gather_blocked(bins, eui, ps, pe, pd,
                                             num_nodes=n, block=blk))


# ---------------------------------------------------------------------------
# pcpm — Partition-Centric, blocked hierarchical gather (paper algs. 4+5)
# ---------------------------------------------------------------------------
def _build_pcpm(g: Graph, cfg: PlanConfig) -> GraphPlan:
    png = shared_png(g, cfg.part_size)
    with build_phase("schedule"):
        sched = build_gather_schedule(png, block=cfg.gather_block)
    return GraphPlan(png=png, schedule=sched, **_plan_fields(g, cfg))


def _pcpm_device(plan: GraphPlan):
    dev = plan._device.get("pcpm")
    if dev is None:
        s = plan.schedule
        dev = _upload(plan.png.update_src, s.edge_update_idx_padded,
                      s.piece_start, s.piece_end, s.piece_dst,
                      s.window_start)
        plan._device["pcpm"] = dev
    return dev


def _window_rows_device(plan: GraphPlan):
    """The window kernel's SMEM stream, each arc's window row
    (``kernels/pcpm_expand.arc_rows``), made on the device from the
    uploaded indices once per plan, in the ``upload`` phase."""
    rows = plan._device.get("pcpm_rows")
    if rows is None:
        from ..kernels.pcpm_expand import arc_rows
        _, eui, _, _, _, win = _pcpm_device(plan)
        with build_phase("upload"):
            rows = jax.block_until_ready(arc_rows(eui, win))
        plan._device["pcpm_rows"] = rows
    return rows


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def window_engages(schedule: GatherSchedule) -> bool:
    """Whether the pcpm pass of one rank column expands through the
    VMEM-window kernel (``kernels/pcpm_expand``): on a TPU, where the
    schedule has windows and the window fits the kernel's VMEM budget.
    XLA's element gather runs otherwise, and for several columns."""
    from ..kernels.pcpm_expand import fits
    return (schedule.window_start is not None
            and fits(schedule.window_rows, schedule.kernel_block)
            and _on_tpu())


def _pcpm_pass(upd, eui, ps, pe, pd, win, rows, x, *, num_nodes: int,
               block: int, window_rows: int):
    """The pcpm pass: scatter, expand, reduce.  The expand takes the
    window kernel where the plan bound its row stream ``rows`` (the
    kernel engages) and ``x`` is one column."""
    from .spmv import (pcpm_expand, pcpm_expand_windowed, pcpm_reduce,
                       pcpm_scatter)
    bins = pcpm_scatter(upd, x)
    if rows is not None and x.ndim == 1:
        vals = pcpm_expand_windowed(bins, eui, win, rows,
                                    window_rows=window_rows)
    else:
        vals = pcpm_expand(bins, eui)
    return pcpm_reduce(vals, ps, pe, pd, num_nodes=num_nodes, block=block)


def _spmv_pcpm(plan: GraphPlan):
    s = plan.schedule
    rows = _window_rows_device(plan) if window_engages(s) else None
    return _streams_fn(_pcpm_pass, *_pcpm_device(plan), rows,
                       num_nodes=plan.num_nodes, block=s.block,
                       window_rows=s.window_rows)


def expand_stats(plan: GraphPlan) -> dict:
    """Counts of a pcpm plan's expand, per pass of one rank column:
    the share of arcs gathered from a resident window (1.0 where the
    window kernel engages, else 0), the window fills, and the pad
    arcs the kernel blocks add over the arcs."""
    s = plan.schedule
    on = window_engages(s)
    loads = 0
    if on:
        w = s.window_start
        loads = 1 + int(np.count_nonzero(w[1:] != w[:-1]))
    pad = len(s.edge_update_idx_padded) - s.num_edges
    return {"pcpm_expand_window_share": 1.0 if on else 0.0,
            "pcpm_expand_window_loads": loads,
            "pcpm_expand_pad_share": pad / max(s.num_edges, 1)}


def _phases_pcpm(plan: GraphPlan):
    from .spmv import pcpm_gather_blocked, pcpm_scatter
    upd, eui, ps, pe, pd, _ = _pcpm_device(plan)
    n, blk = plan.num_nodes, plan.schedule.block
    return (lambda x: pcpm_scatter(upd, x),
            lambda bins: pcpm_gather_blocked(bins, eui, ps, pe, pd,
                                             num_nodes=n, block=blk))


# ---------------------------------------------------------------------------
# pcpm_pallas — the Pallas gather kernel path (kernels/pcpm_spmv)
# ---------------------------------------------------------------------------
def _pallas_part_size(g: Graph, requested: Optional[int]) -> int:
    """The largest power-of-two partition whose kernel working set
    fits VMEM.  Sizes past the graph's (power-of-two) node count are
    one partition either way and are cut to it; an explicit size that
    still cannot fit raises here, at plan build."""
    from ..kernels.pcpm_spmv.kernel import check_part_size, max_part_size
    whole = 1 << max(g.num_nodes - 1, 7).bit_length()
    if requested is None:
        return min(max_part_size(), whole)
    requested = min(requested, whole)
    check_part_size(requested)
    return requested


def _build_pcpm_pallas(g: Graph, cfg: PlanConfig) -> GraphPlan:
    png = shared_png(g, cfg.part_size)
    return GraphPlan(png=png, blocked=block_png(png),
                     **_plan_fields(g, cfg))


def _packed_device(plan: GraphPlan):
    dev = plan._device.get("packed")
    if dev is None:
        from ..kernels.pcpm_spmv import pack_blocked
        dev = pack_blocked(plan.blocked, plan.num_nodes)
        plan._device["packed"] = dev
    return dev


def _spmv_pcpm_pallas(plan: GraphPlan):
    from ..kernels.pcpm_spmv import pcpm_spmv_pallas
    return jax.tree_util.Partial(pcpm_spmv_pallas, _packed_device(plan))


# ---------------------------------------------------------------------------
# pcpm_sharded — multi-device all-to-all PCPM (core/distributed.py)
# ---------------------------------------------------------------------------
def _build_pcpm_sharded(g: Graph, cfg: PlanConfig) -> GraphPlan:
    from .distributed import build_sharded_png
    layout = build_sharded_png(g, cfg.num_shards,
                               gather_block=cfg.gather_block)
    return GraphPlan(sharded=layout, **_plan_fields(g, cfg))


def _spmv_pcpm_sharded(plan: GraphPlan):
    from .distributed import pcpm_all_to_all_spmv
    axis = plan.config.shard_axis
    key = ("sharded_spmv", axis)
    spmv = plan._device.get(key)
    if spmv is None:
        spmv = pcpm_all_to_all_spmv(plan.sharded, sharded_mesh(plan, axis),
                                    axis)
        plan._device[key] = spmv
    return _streams_fn(_padded_apply, spmv, num_nodes=plan.num_nodes,
                       padded=plan.sharded.padded_nodes)


def _padded_apply(spmv, x, *, num_nodes: int, padded: int):
    width = ((0, padded - num_nodes),) + ((0, 0),) * (x.ndim - 1)
    return spmv(jnp.pad(x, width))[:num_nodes]


# ---------------------------------------------------------------------------
# Incremental patchers (stream/patch.py) — imported lazily: the stream
# package imports this registry, so the hook bodies must not import it
# at module load.
# ---------------------------------------------------------------------------
def _patch_pdpr(plan, g_new, delta):
    from ..stream.patch import patch_pdpr_plan
    return patch_pdpr_plan(plan, g_new, delta)


def _patch_bvgas(plan, g_new, delta):
    from ..stream.patch import patch_bvgas_plan
    return patch_bvgas_plan(plan, g_new, delta)


def _patch_pcpm(plan, g_new, delta):
    from ..stream.patch import patch_pcpm_plan
    return patch_pcpm_plan(plan, g_new, delta)


def _patch_pcpm_pallas(plan, g_new, delta):
    from ..stream.patch import patch_pcpm_pallas_plan
    return patch_pcpm_pallas_plan(plan, g_new, delta)


# ---------------------------------------------------------------------------
for _backend in (
    Backend("pdpr", _build_pdpr, _spmv_pdpr, uses_gather_block=True,
            patch_plan=_patch_pdpr, supports_push_query=True),
    Backend("bvgas", _build_bvgas, _spmv_bvgas, uses_gather_block=True,
            phase_fns=_phases_bvgas, patch_plan=_patch_bvgas,
            supports_push_query=True),
    Backend("pcpm", _build_pcpm, _spmv_pcpm, uses_gather_block=True,
            phase_fns=_phases_pcpm, patch_plan=_patch_pcpm,
            supports_push_query=True),
    Backend("pcpm_pallas", _build_pcpm_pallas, _spmv_pcpm_pallas,
            patch_plan=_patch_pcpm_pallas, supports_push_query=True,
            part_size=_pallas_part_size),
    # pcpm_sharded has no patcher: shard-local receive buffers and the
    # all-to-all send schedule are global layouts (a delta anywhere can
    # grow any shard's wire stream), so deltas fall back to a full
    # rebuild — the residual-push warm start still applies.  No push
    # queries either: the (n,) query state is single-device.
    Backend("pcpm_sharded", _build_pcpm_sharded, _spmv_pcpm_sharded,
            supports_sharding=True, uses_gather_block=True),
):
    register_backend(_backend)
