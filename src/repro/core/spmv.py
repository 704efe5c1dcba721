"""The three SpMV engines from the paper, in JAX.

All compute  y = A^T @ x  for the (possibly multi-)vector x — PageRank
uses x = scaled ranks, GNNs use x = node features (n, d).

- ``pdpr``  : pull-direction baseline (alg. 1) — per-destination gather
              of source values, i.e. segment-sum over CSC order.
- ``bvgas`` : Binning w/ Vertex-centric GAS (alg. 2) — scatter phase
              materializes one update PER EDGE into dst-partition-major
              bins; gather phase segment-sums them.
- ``pcpm``  : Partition-Centric (algs. 4+5) — scatter phase materializes
              one update PER (src, dst-partition) pair (the PNG update
              stream, m/r entries); gather expands updates over edges via
              the ``edge_update_idx`` stream and segment-sums.

The two-phase engines intentionally keep scatter and gather as separate
jitted stages so the bins round-trip through HBM exactly as the paper's
bins round-trip through DRAM; ``fused=True`` collapses them into one XLA
program (a beyond-paper optimization measured in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs.formats import Graph
from .partition import Partitioning
from .png import (GatherSchedule, PNGLayout, build_png,
                  build_gather_schedule)


# ---------------------------------------------------------------------------
# Device-resident layouts
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DeviceCSC:
    """Edges sorted by destination (pull order)."""
    num_nodes: int
    src: jnp.ndarray   # (m,) int32, sorted by dst
    dst: jnp.ndarray   # (m,) int32, ascending

    @staticmethod
    def build(g: Graph) -> "DeviceCSC":
        order = np.lexsort((g.src, g.dst))
        return DeviceCSC(g.num_nodes, jnp.asarray(g.src[order]),
                         jnp.asarray(g.dst[order]))


@dataclasses.dataclass(frozen=True)
class DeviceBVGAS:
    """Edges sorted by destination partition (BVGAS deterministic layout:
    dst ids are written once, then reused every iteration)."""
    num_nodes: int
    src: jnp.ndarray   # (m,) int32, dst-partition-major
    dst: jnp.ndarray   # (m,) int32

    @staticmethod
    def build(g: Graph, part: Partitioning) -> "DeviceBVGAS":
        dstp = g.dst.astype(np.int64) // part.part_size
        order = np.lexsort((g.dst, g.src, dstp))
        return DeviceBVGAS(g.num_nodes, jnp.asarray(g.src[order]),
                           jnp.asarray(g.dst[order]))


@dataclasses.dataclass(frozen=True)
class DevicePNG:
    """Flat PNG streams on device (see core/png.py), plus the blocked
    gather schedule (piece bounds over the dst-sorted edge stream)."""
    num_nodes: int
    update_src: jnp.ndarray       # (U,) int32
    edge_update_idx: jnp.ndarray  # (M,) int32
    edge_dst: jnp.ndarray         # (M,) int32, ascending
    compression_ratio: float
    # blocked-gather schedule (see png.build_gather_schedule)
    gather_block: int
    eui_padded: jnp.ndarray       # (Mp,) int32
    piece_start: jnp.ndarray      # (P0,) int32
    piece_end: jnp.ndarray        # (P0,) int32
    piece_dst: jnp.ndarray        # (P0,) int32, pad = num_nodes

    @staticmethod
    def build(g: Graph, part: Partitioning,
              layout: PNGLayout | None = None, *,
              gather_block: int = 256) -> "DevicePNG":
        layout = layout or build_png(g, part)
        sched = build_gather_schedule(layout, block=gather_block)
        return DevicePNG(layout.num_nodes,
                         jnp.asarray(layout.update_src),
                         jnp.asarray(layout.edge_update_idx),
                         jnp.asarray(layout.edge_dst),
                         layout.compression_ratio,
                         sched.block,
                         jnp.asarray(sched.edge_update_idx_padded),
                         jnp.asarray(sched.piece_start),
                         jnp.asarray(sched.piece_end),
                         jnp.asarray(sched.piece_dst))


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("num_nodes",))
def pdpr_spmv(src: jnp.ndarray, dst: jnp.ndarray, x: jnp.ndarray,
              *, num_nodes: int) -> jnp.ndarray:
    """Pull-direction SpMV: y[v] = sum_{(u,v) in E} x[u]."""
    return jax.ops.segment_sum(x[src], dst, num_segments=num_nodes)


@partial(jax.jit, static_argnames=())
def bvgas_scatter(src: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Scatter: one update per edge, written to dst-partition-major bins."""
    return x[src]


@partial(jax.jit, static_argnames=("num_nodes",))
def bvgas_gather(bins: jnp.ndarray, dst: jnp.ndarray,
                 *, num_nodes: int) -> jnp.ndarray:
    return jax.ops.segment_sum(bins, dst, num_segments=num_nodes)


@partial(jax.jit, static_argnames=())
def pcpm_scatter(update_src: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Scatter: ONE update per (src, dst-partition) — the PNG compression.
    Update bins are m/r entries instead of m."""
    with jax.named_scope("pcpm.scatter"):
        return x[update_src]


@partial(jax.jit, static_argnames=("num_nodes",))
def pcpm_gather(update_bins: jnp.ndarray, edge_update_idx: jnp.ndarray,
                edge_dst: jnp.ndarray, *, num_nodes: int) -> jnp.ndarray:
    """Gather: expand each update over its in-partition destinations
    (branch-free analogue of the MSB stream) and accumulate.

    Flat element-wise scatter-add — kept as the shape-agnostic fallback
    and for the paper's two-phase timing; the hot path is
    ``pcpm_gather_blocked``.
    """
    return jax.ops.segment_sum(update_bins[edge_update_idx], edge_dst,
                               num_segments=num_nodes)


@jax.jit
def pcpm_expand(update_bins: jnp.ndarray,
                eui_padded: jnp.ndarray) -> jnp.ndarray:
    """The per-arc read of the update bins, XLA's element gather
    (named scope ``pcpm.expand``): (Mp,) or (Mp, d)."""
    with jax.named_scope("pcpm.expand"):
        return update_bins[eui_padded]


@partial(jax.jit, static_argnames=("window_rows",))
def pcpm_expand_windowed(update_bins: jnp.ndarray, eui_padded: jnp.ndarray,
                         window_start: jnp.ndarray, arc_rows: jnp.ndarray,
                         *, window_rows: int) -> jnp.ndarray:
    """``pcpm_expand`` of one rank column by the Pallas kernel that
    reads each arc's update from a VMEM-resident window of its
    destination partition's bins (``kernels/pcpm_expand``); ``arc_rows``
    is each arc's window row, made once per plan."""
    from ..kernels.pcpm_expand import window_expand
    with jax.named_scope("pcpm.expand"):
        return window_expand(update_bins, eui_padded, window_start,
                             arc_rows, window_rows=window_rows)


@partial(jax.jit, static_argnames=("num_nodes", "block"))
def pcpm_reduce(vals: jnp.ndarray, piece_start: jnp.ndarray,
                piece_end: jnp.ndarray, piece_dst: jnp.ndarray, *,
                num_nodes: int, block: int) -> jnp.ndarray:
    """The per-destination sum of the expanded values (named scope
    ``pcpm.reduce``): per-block inclusive prefix sums turn each
    destination's run into a difference of two gathers; only the
    ~n + M/block run sums hit the element-wise scatter-add."""
    with jax.named_scope("pcpm.reduce"):
        nb = vals.shape[0] // block
        local = jnp.cumsum(
            vals.reshape((nb, block) + vals.shape[1:]), axis=1
        ).reshape(vals.shape)
        lead = local[piece_end]
        prev = local[jnp.maximum(piece_start - 1, 0)]
        at_block_start = piece_start % block == 0
        if vals.ndim > 1:
            at_block_start = at_block_start[:, None]
        piece_sum = lead - jnp.where(at_block_start, 0, prev)
        return jax.ops.segment_sum(piece_sum, piece_dst,
                                   num_segments=num_nodes + 1,
                                   indices_are_sorted=True)[:num_nodes]


@partial(jax.jit, static_argnames=("num_nodes", "block"))
def pcpm_gather_blocked(update_bins: jnp.ndarray, eui_padded: jnp.ndarray,
                        piece_start: jnp.ndarray, piece_end: jnp.ndarray,
                        piece_dst: jnp.ndarray, *, num_nodes: int,
                        block: int) -> jnp.ndarray:
    """Hierarchical gather over the dst-sorted stream (DESIGN.md §3):
    ``pcpm_expand`` then ``pcpm_reduce``.  ~9x faster than the flat
    ``pcpm_gather`` on the CPU at bench scale, identical to f32
    rounding."""
    return pcpm_reduce(pcpm_expand(update_bins, eui_padded), piece_start,
                       piece_end, piece_dst, num_nodes=num_nodes,
                       block=block)


@partial(jax.jit, static_argnames=("num_nodes", "fused"))
def pcpm_spmv(png_update_src, png_edge_update_idx, png_edge_dst, x,
              *, num_nodes: int, fused: bool = True) -> jnp.ndarray:
    """Two-phase PCPM SpMV.  ``fused=True`` (default) lets XLA fuse the
    scatter into the gather's expansion; ``fused=False`` places an
    optimization barrier between the phases so the m/r-entry update bins
    materialize in HBM, reproducing the paper's bins-round-trip-through-
    DRAM structure inside a single program."""
    bins = pcpm_scatter(png_update_src, x)
    if not fused:
        bins = jax.lax.optimization_barrier(bins)
    return pcpm_gather(bins, png_edge_update_idx, png_edge_dst,
                       num_nodes=num_nodes)


# Weighted variant (paper §VII extension: weights travel with dest IDs).
@partial(jax.jit, static_argnames=("num_nodes",))
def pcpm_spmv_weighted(png_update_src, png_edge_update_idx, png_edge_dst,
                       edge_weight, x, *, num_nodes: int) -> jnp.ndarray:
    bins = x[png_update_src]
    vals = bins[png_edge_update_idx]
    if x.ndim > 1:
        vals = vals * edge_weight[:, None]
    else:
        vals = vals * edge_weight
    return jax.ops.segment_sum(vals, png_edge_dst, num_segments=num_nodes)


# ---------------------------------------------------------------------------
# Engine wrapper with a uniform API
# ---------------------------------------------------------------------------
class SpMVEngine:
    """y = A^T x with a fixed graph — a thin shim over the plan/run
    split (DESIGN.md §8): construction resolves ``method`` through the
    backend registry (``core.backends``) and fetches the preprocessing
    artifact from the process-level plan cache (``core.plan``), so two
    engines on the same ``(graph, config)`` share ONE ``GraphPlan``
    (layouts sorted once, device streams uploaded once).

    ``method`` is any registered backend — the built-ins are the three
    paper engines (pdpr, bvgas, pcpm), the Pallas-kernel PCPM path
    (pcpm_pallas) and the multi-device all-to-all PCPM path
    (pcpm_sharded; vertex-sharded over ``num_shards`` devices, default
    all of them).  A prebuilt/loaded ``plan`` overrides the knob
    arguments.  New code should prefer ``repro.open`` (repro/api.py).
    """

    def __init__(self, g: Graph, *, method: str = "pcpm",
                 part_size: int | None = None, two_phase: bool = False,
                 num_shards: int | None = None, plan=None):
        from . import backends
        from .plan import PlanConfig, build_plan, validate_plan
        if plan is None:
            plan = build_plan(g, PlanConfig(
                method=method, part_size=part_size,
                num_shards=num_shards))
        else:
            validate_plan(g, plan)
            if plan.sharded is not None:
                backends.check_device_count(plan.sharded.num_shards)
        self.plan = plan
        self.method = plan.method
        self.backend = backends.get_backend(plan.method)
        if two_phase and not self.backend.supports_two_phase:
            raise ValueError(
                f"two_phase=True is only meaningful for the two-phase "
                f"engines; backend {self.method!r} does not support it")
        self.num_nodes = plan.num_nodes
        self.num_edges = plan.num_edges
        self.two_phase = two_phase
        self.partitioning = plan.partitioning
        # mesh axis name — the plan's (normalized) axis, so the fused
        # drivers, serving paths and the spmv closure all share ONE
        # mesh and one compiled all-to-all program
        self.shard_axis = plan.config.shard_axis

    # ------------------------------------------------------ plan views
    @property
    def layout(self) -> PNGLayout:
        """The PNG layout (pcpm/pcpm_pallas plans)."""
        if self.plan.png is None:
            raise AttributeError(
                f"backend {self.method!r} has no PNG layout")
        return self.plan.png

    @property
    def sharded_layout(self):
        if self.plan.sharded is None:
            raise AttributeError(
                f"backend {self.method!r} has no sharded layout")
        return self.plan.sharded

    @property
    def mesh(self):
        from . import backends
        return backends.sharded_mesh(self.plan, self.shard_axis)

    @property
    def compression_ratio(self) -> float:
        return self.plan.compression_ratio

    @property
    def _fused_cache(self) -> dict:
        # plan-level, so every engine/driver on one plan shares traces
        from . import backends
        return backends.fused_loop_cache(self.plan)

    def spmv_fn(self):
        """A pure, traceable ``x -> A^T x`` closure over the plan's
        device-resident streams — what the fused `lax.while_loop`
        PageRank driver and AOT compilation consume.  Raises for
        ``two_phase`` engines rather than silently dropping the phase
        barrier (a host-side barrier has no meaning under jit).

        For reordered plans (``plan.reorder_perm`` set) this closure
        operates in INTERNAL (relabeled) space — fused consumers
        iterate there and map results once at the boundary
        (``core.plan.internal_graph`` / ``backends.reorder_device``);
        ``__call__`` is the original-space per-pass wrapper."""
        if self.two_phase:
            raise ValueError(
                "a two_phase engine cannot provide a fused spmv_fn: "
                "the host-side phase barrier does not exist under jit."
                " Construct the engine with two_phase=False for fused/"
                "serving consumers.")
        from . import backends
        return backends.spmv_fn(self.plan)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        from . import backends
        fn = (backends.two_phase_spmv_fn(self.plan) if self.two_phase
              # host barrier between scatter and gather: the backend's
              # own two_phase_fn (bins round-trip through HBM exactly
              # as the paper's bins round-trip through DRAM)
              else backends.spmv_fn(self.plan))
        if self.plan.reorder_perm is None:
            return fn(x)
        # reordered plan: the layouts index the relabeled graph, so map
        # x into internal space and the result back — callers see the
        # original labeling.  Fused consumers skip this by iterating in
        # internal space via spmv_fn() and mapping once at the end.
        perm, inv = backends.reorder_device(self.plan)
        y = fn(jnp.take(jnp.asarray(x), inv, axis=0))
        return jnp.take(y, perm, axis=0)
