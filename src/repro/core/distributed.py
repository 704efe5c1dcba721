"""Distributed PCPM: the paper's communication-volume reduction lifted
from DRAM traffic to interconnect traffic (DESIGN.md §6).

Vertices are sharded contiguously over a mesh axis.  The PNG build at
shard granularity produces, per (source-shard s, destination-shard t),
the DEDUPLICATED update list — each source vertex's value crosses the
wire once per destination shard instead of once per cross-shard edge
(compression r on the wire).  The scatter phase is one all-to-all of
dense compressed buffers; the gather phase is the shard-local blocked
hierarchical reduction of DESIGN.md §3 over a dst-sorted edge stream.

``sharded_power_iteration`` is the device-resident iteration engine:
the WHOLE power iteration is one donated, jitted ``lax.while_loop``
whose body runs scatter + all-to-all + blocked gather under
``shard_map``; the L1 residual (and dangling-node mass) is combined
across shards with ``psum`` so ``tol`` early exit is decided on device
with zero host round-trips (DESIGN.md §6).

``edge_cut_spmv`` is the distributed BVGAS analogue (one update PER
EDGE on the wire) used as the communication baseline.

``ShardedPNG`` is a plan-layer artifact: the ``pcpm_sharded`` backend
(core/backends.py) builds it into the process-cached ``GraphPlan``
(core/plan.py), which also serializes it — consumers get it via
``engine.sharded_layout`` / ``plan.sharded`` rather than calling
``build_sharded_png`` directly (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graphs.formats import Graph
from .png import flat_gather_schedule
from .spmv import pcpm_gather_blocked


# ---------------------------------------------------------------- layout
@dataclasses.dataclass(frozen=True)
class ShardedPNG:
    """Static-shape sharded PNG (leading axis = owning shard).

    send_ids  (S, S, U) int32: send_ids[s, t] = local ids shard s sends
                               to shard t (pad -1 -> zero value)
    edge_upd  (S, E) int32:    per dst shard, index into its receive
                               buffer (concat over s, row-major), pad
                               points at S*U (zero slot); dst-sorted
                               within each shard
    edge_dst  (S, E) int32:    local destination ids, ascending per
                               shard, pad = shard_size

    plus the per-shard blocked gather schedule (DESIGN.md §3 applied
    shard-locally): the dst-sorted stream padded to a ``gather_block``
    multiple and cut into contiguous same-destination runs.
    """
    num_shards: int
    shard_size: int
    num_nodes: int
    send_ids: np.ndarray
    edge_upd: np.ndarray
    edge_dst: np.ndarray
    # blocked gather schedule, per shard
    gather_block: int
    eui_padded: np.ndarray     # (S, Mp) int32, pad -> S*U zero slot
    piece_start: np.ndarray    # (S, P0) int32
    piece_end: np.ndarray      # (S, P0) int32
    piece_dst: np.ndarray      # (S, P0) int32, pad = shard_size
    # stats
    wire_updates: int      # deduplicated cross-shard update count (PCPM)
    wire_edges: int        # cross-shard edge count (edge-cut baseline)

    @property
    def wire_compression(self) -> float:
        return self.wire_edges / max(self.wire_updates, 1)

    @property
    def padded_nodes(self) -> int:
        return self.num_shards * self.shard_size


def build_sharded_png(g: Graph, num_shards: int, *,
                      gather_block: int = 256) -> ShardedPNG:
    shard_size = -(-g.num_nodes // num_shards)
    src = g.src.astype(np.int64)
    dst = g.dst.astype(np.int64)
    s_sh = src // shard_size
    d_sh = dst // shard_size

    # --- dedup (src, dst_shard) pairs, grouped by (src_shard, dst_shard)
    order = np.lexsort((src, s_sh, d_sh))
    src_o, dst_o, ssh_o, dsh_o = (src[order], dst[order], s_sh[order],
                                  d_sh[order])
    pair_key = (dsh_o * num_shards + ssh_o) * g.num_nodes + src_o
    new = np.empty(len(pair_key), dtype=bool)
    if len(pair_key):
        new[0] = True
        np.not_equal(pair_key[1:], pair_key[:-1], out=new[1:])
    # rank of each update within its (s, t) group
    grp_key = dsh_o * num_shards + ssh_o
    upd_idx_global = np.cumsum(new) - 1
    grp_of_upd = grp_key[new]
    # per-update rank within its group
    grp_first_upd = np.zeros(grp_of_upd.shape[0], dtype=np.int64)
    if len(grp_of_upd):
        starts = np.flatnonzero(np.r_[True, grp_of_upd[1:]
                                      != grp_of_upd[:-1]])
        sizes = np.diff(np.r_[starts, len(grp_of_upd)])
        grp_first_upd = np.repeat(
            np.arange(len(grp_of_upd))[starts], sizes)
    upd_rank = np.arange(len(grp_of_upd)) - grp_first_upd

    counts = np.zeros(num_shards * num_shards, dtype=np.int64)
    np.add.at(counts, grp_of_upd, 1)
    u_max = max(int(counts.max(initial=0)), 1)

    send_ids = np.full((num_shards, num_shards, u_max), -1, dtype=np.int32)
    upd_src = src_o[new]
    upd_ssh = ssh_o[new]
    upd_dsh = dsh_o[new]
    send_ids[upd_ssh, upd_dsh, upd_rank] = (upd_src
                                            - upd_ssh * shard_size)

    # --- per-dst-shard edge streams referencing the receive buffer.
    # Receive buffer at shard t: rows s = send_ids[s, t] -> flat s*U + r.
    upd_slot = upd_ssh * u_max + upd_rank          # slot within dst buffer
    edge_slot = upd_slot[upd_idx_global]           # per edge (sorted order)
    # Re-sort the gather stream by destination node within each shard so
    # the shard-local gather can use the blocked run reduction
    # (DESIGN.md §3); edge_slot still points at the same receive slots.
    gorder = np.lexsort((dst_o, dsh_o))
    dsh_g = dsh_o[gorder]
    dst_g = dst_o[gorder]
    slot_g = edge_slot[gorder]
    e_counts = np.zeros(num_shards, dtype=np.int64)
    np.add.at(e_counts, dsh_g, 1)
    e_max = max(int(e_counts.max(initial=0)), 1)
    zero_slot = num_shards * u_max
    edge_upd = np.full((num_shards, e_max), zero_slot, dtype=np.int32)
    edge_dst = np.full((num_shards, e_max), shard_size, dtype=np.int32)
    e_first = np.zeros(len(dsh_g), dtype=np.int64)
    if len(dsh_g):
        starts = np.flatnonzero(np.r_[True, dsh_g[1:] != dsh_g[:-1]])
        sizes = np.diff(np.r_[starts, len(dsh_g)])
        e_first = np.repeat(np.arange(len(dsh_g))[starts], sizes)
    e_rank = np.arange(len(dsh_g)) - e_first
    edge_upd[dsh_g, e_rank] = slot_g
    edge_dst[dsh_g, e_rank] = dst_g - dsh_g * shard_size

    # --- per-shard blocked gather schedule over the dst-sorted streams
    scheds = [flat_gather_schedule(edge_upd[s], edge_dst[s],
                                   num_nodes=shard_size,
                                   block=gather_block,
                                   pad_update=zero_slot)
              for s in range(num_shards)]
    p_max = max(len(sc[1]) for sc in scheds)
    eui_padded = np.stack([sc[0] for sc in scheds])
    piece_start = np.zeros((num_shards, p_max), dtype=np.int32)
    piece_end = np.zeros((num_shards, p_max), dtype=np.int32)
    piece_dst = np.full((num_shards, p_max), shard_size, dtype=np.int32)
    for s, (_, st, en, pd) in enumerate(scheds):
        # pad pieces re-read run [0, 0] but carry the sentinel dst, so
        # the segment-sum drops them — mathematically inert
        piece_start[s, :len(st)] = st
        piece_end[s, :len(en)] = en
        piece_dst[s, :len(pd)] = pd

    wire_updates = int(np.sum(upd_ssh != upd_dsh))
    wire_edges = int(np.sum(s_sh != d_sh))
    return ShardedPNG(num_shards, shard_size, g.num_nodes,
                      send_ids, edge_upd, edge_dst,
                      gather_block, eui_padded, piece_start, piece_end,
                      piece_dst, wire_updates, wire_edges)


# --------------------------------------------------------------- engines
def _place(mesh: Mesh, axis: str, *arrays) -> tuple:
    """Put each host array on the mesh, its leading (shard) axis
    split over ``axis`` — once, so programs take them as arguments and
    never carry them as constants."""
    return tuple(jax.device_put(a, NamedSharding(
        mesh, P(axis, *([None] * (np.ndim(a) - 1))))) for a in arrays)


def _scatter_all_to_all(x_l, send_l, axis, *, num_shards, shard_size,
                        u_max):
    """Shard-local scatter + wire phase: gather this shard's dedup send
    buffers from local values and all-to-all them.  Returns the receive
    buffer (S*U + 1, d) with a trailing zero slot for pad edges."""
    ids = send_l[0]                                    # (S, U)
    bufs = x_l[jnp.clip(ids, 0, shard_size - 1)] * (ids >= 0)[..., None]
    recv = jax.lax.all_to_all(bufs, axis, 0, 0, tiled=True)
    recv = recv.reshape(num_shards * u_max, x_l.shape[-1])
    return jnp.concatenate(
        [recv, jnp.zeros((1, recv.shape[-1]), recv.dtype)], 0)


def pcpm_all_to_all_spmv(layout: ShardedPNG, mesh: Mesh, axis: str, *,
                         blocked: bool = True):
    """Returns a jitted y = A^T x over vertex-sharded x (padded to
    S * shard_size).  x: (n_pad,) or (n_pad, d).

    ``blocked=True`` (default) runs the shard-local gather as the
    hierarchical blocked reduction over the dst-sorted stream
    (DESIGN.md §3); ``blocked=False`` keeps the flat segment-sum as a
    debug fallback.
    """
    s, u = layout.num_shards, layout.send_ids.shape[2]
    ssz = layout.shard_size
    blk = layout.gather_block
    # (S, S, U) send ids, (S, E) edge streams, (S, Mp)/(S, P0) schedule
    streams = _place(mesh, axis, layout.send_ids, layout.edge_upd,
                     layout.edge_dst, layout.eui_padded,
                     layout.piece_start, layout.piece_end,
                     layout.piece_dst)
    vec = P(axis)
    mat = P(axis, None)

    def local(x_l, send_l, eu_l, ed_l, eui_l, ps_l, pe_l, pd_l):
        x_l = x_l.reshape(ssz, -1)
        recv = _scatter_all_to_all(x_l, send_l, axis, num_shards=s,
                                   shard_size=ssz, u_max=u)
        if blocked:
            return pcpm_gather_blocked(recv, eui_l[0], ps_l[0], pe_l[0],
                                       pd_l[0], num_nodes=ssz, block=blk)
        vals = recv[eu_l[0]]                               # (E, d)
        y = jax.ops.segment_sum(vals, ed_l[0], num_segments=ssz + 1)
        return y[:ssz]

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(vec, P(axis, None, None), mat, mat, mat,
                                 mat, mat, mat),
                       out_specs=vec, check_vma=False)

    @jax.jit
    def spmv(streams, x):
        squeeze = x.ndim == 1
        xs = x[:, None] if squeeze else x
        y = fn(xs, *streams)
        return y[:, 0] if squeeze else y

    return jax.tree_util.Partial(spmv, streams)


def edge_cut_spmv(g: Graph, num_shards: int, mesh: Mesh, axis: str):
    """Distributed BVGAS baseline: one update PER cross-shard edge on
    the wire (no dedup).  Send buffers are per-edge values grouped by
    destination shard."""
    shard_size = -(-g.num_nodes // num_shards)
    src, dst = g.src.astype(np.int64), g.dst.astype(np.int64)
    s_sh, d_sh = src // shard_size, dst // shard_size
    order = np.lexsort((dst, d_sh, s_sh))
    src_o, dst_o = src[order], dst[order]
    ssh_o, dsh_o = s_sh[order], d_sh[order]
    counts = np.zeros(num_shards * num_shards, dtype=np.int64)
    np.add.at(counts, ssh_o * num_shards + dsh_o, 1)
    e_max = max(int(counts.max(initial=0)), 1)
    send_src = np.full((num_shards, num_shards, e_max), -1, np.int32)
    send_dst = np.full((num_shards, num_shards, e_max), shard_size,
                       np.int32)
    grp = ssh_o * num_shards + dsh_o
    first = np.zeros(len(grp), dtype=np.int64)
    if len(grp):
        starts = np.flatnonzero(np.r_[True, grp[1:] != grp[:-1]])
        sizes = np.diff(np.r_[starts, len(grp)])
        first = np.repeat(np.arange(len(grp))[starts], sizes)
    rank = np.arange(len(grp)) - first
    send_src[ssh_o, dsh_o, rank] = src_o - ssh_o * shard_size
    send_dst[ssh_o, dsh_o, rank] = dst_o - dsh_o * shard_size

    send_src_j = jnp.asarray(send_src)
    send_dst_j = jnp.asarray(send_dst)
    vec, mat = P(axis), P(axis, None, None)

    def local(x_l, ss_l, sd_l):
        x_l = x_l.reshape(shard_size, -1)
        d = x_l.shape[-1]
        ids = ss_l[0]                                     # (S, E)
        bufs = x_l[jnp.clip(ids, 0, shard_size - 1)] * \
            (ids >= 0)[..., None]                          # (S, E, d)
        dsts = sd_l[0]                                    # (S, E) local dst
        recv_v = jax.lax.all_to_all(bufs, axis, 0, 0, tiled=True)
        recv_d = jax.lax.all_to_all(dsts, axis, 0, 0, tiled=True)
        y = jax.ops.segment_sum(recv_v.reshape(-1, d),
                                recv_d.reshape(-1),
                                num_segments=shard_size + 1)
        return y[:shard_size]

    fn = jax.shard_map(local, mesh=mesh, in_specs=(vec, mat, mat),
                       out_specs=vec, check_vma=False)

    @jax.jit
    def spmv(x):
        squeeze = x.ndim == 1
        xs = x[:, None] if squeeze else x
        y = fn(xs, send_src_j, send_dst_j)
        return y[:, 0] if squeeze else y

    return spmv


def pad_to_shards(x: np.ndarray, layout: ShardedPNG) -> np.ndarray:
    n_pad = layout.num_shards * layout.shard_size
    pad = n_pad - x.shape[0]
    width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return np.pad(x, width)


# ----------------------------------------------- fused sharded iteration
def _shard_streams(layout: ShardedPNG, mesh: Mesh, axis: str):
    """The static layout streams plus the pad-row mask, each placed
    once with its leading axis split over the mesh — the per-shard
    data every shard_map'd iteration loop (fused batch loop and
    serving chunk stepper alike) takes as arguments.  Passed as
    arguments, not closed over: a closed-over array would be baked
    into the program as a constant on one device."""
    mask_host = np.zeros(layout.padded_nodes, dtype=np.float32)
    mask_host[:layout.num_nodes] = 1.0
    return _place(mesh, axis, layout.send_ids, layout.eui_padded,
                  layout.piece_start, layout.piece_end, layout.piece_dst,
                  mask_host)


def _local_gather_spmv(layout: ShardedPNG, axis: str, send_l, eui_l,
                       ps_l, pe_l, pd_l):
    """The shard-local y = A^T x closure (scatter + all-to-all +
    blocked gather) over the shard_map-sliced stream arguments."""
    s, u = layout.num_shards, layout.send_ids.shape[2]
    ssz, blk = layout.shard_size, layout.gather_block

    def spmv(x2):
        recv = _scatter_all_to_all(x2, send_l, axis, num_shards=s,
                                   shard_size=ssz, u_max=u)
        return pcpm_gather_blocked(recv, eui_l[0], ps_l[0], pe_l[0],
                                   pd_l[0], num_nodes=ssz, block=blk)

    return spmv


def sharded_power_iteration(layout: ShardedPNG, mesh: Mesh, axis: str,
                            *, damping: float = 0.85,
                            num_iterations: int = 20, tol: float = 0.0,
                            check_every: int = 1, multi: bool = False,
                            dangling: str = "none"):
    """Device-resident sharded PageRank loop (DESIGN.md §6).

    Returns a jitted ``run(pr0, inv_deg, base) -> (pr, it, residuals)``
    over PADDED, vertex-sharded arrays (``n_pad = S * shard_size``):
    ``pr0`` is donated, ``base`` is the already-(1-damping)-scaled
    teleport vector (zero in pad slots).  The whole iteration is ONE
    ``lax.while_loop`` under ``shard_map``:

    - scatter + all-to-all + shard-local blocked gather per step;
    - the L1 residual is psum-combined so the ``tol``/``check_every``
      early exit is a replicated on-device decision — no host syncs;
    - ``dangling="redistribute"`` psum-combines the rank mass parked on
      zero-out-degree nodes each step and redistributes it over the
      teleport distribution (``base / (1 - damping)``), conserving
      total mass at 1;
    - the pad-slot mask is a precomputed sharded constant (the seed
      rebuilt a host-side ``arange(n_pad)`` every iteration).

    With ``multi=True`` the state is (n_pad, d) — d independent rank
    vectors in lockstep; the residual is the max over columns.
    """
    if dangling not in ("none", "redistribute"):
        raise ValueError(f"unknown dangling policy {dangling!r}")
    vec = P(axis)
    state_spec = P(axis, None) if multi else P(axis)

    def local_run(pr, inv_deg, base, mask_l, send_l, eui_l, ps_l, pe_l,
                  pd_l):
        # pr/base: (ssz,) or (ssz, d); inv_deg/mask_l: (ssz,)
        inv_col = inv_deg[:, None] if multi else inv_deg
        mask_col = mask_l[:, None] if multi else mask_l
        # loop-invariant: dangling indicator and the redistribution
        # direction (teleport distribution scaled by damping) — XLA
        # hoists both out of the while body
        dang = (inv_deg == 0).astype(pr.dtype) * mask_l
        dang_col = dang[:, None] if multi else dang
        redist = base * (damping / (1.0 - damping))
        residuals0 = jnp.full((max(num_iterations, 1),), -1.0,
                              dtype=jnp.float32)
        spmv = _local_gather_spmv(layout, axis, send_l, eui_l, ps_l,
                                  pe_l, pd_l)

        def cond(state):
            it, _, _, done = state
            return (it < num_iterations) & ~done

        def body(state):
            it, pr, residuals, done = state
            spr = pr * inv_col                  # scaled ranks (alg.1 l.3)
            y = spmv(spr if multi else spr[:, None])
            y = y if multi else y[:, 0]
            pr_next = base + damping * y
            if dangling == "redistribute":
                dmass = jax.lax.psum((pr * dang_col).sum(axis=0), axis)
                pr_next = pr_next + dmass * redist
            pr_next = pr_next * mask_col
            check = (((it + 1) % check_every == 0)
                     | (it + 1 >= num_iterations))
            res_g = jax.lax.psum(jnp.abs(pr_next - pr).sum(axis=0),
                                 axis)
            res = jnp.where(check, res_g.max() if multi else res_g,
                            -1.0)
            residuals = residuals.at[it].set(res)
            if tol > 0:
                done = done | (check & (res >= 0) & (res < tol))
            return it + 1, pr_next, residuals, done

        it, pr, residuals, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), pr, residuals0, jnp.bool_(False)))
        return pr, it, residuals

    fn = jax.shard_map(local_run, mesh=mesh,
                       in_specs=(state_spec, vec, state_spec, vec,
                                 P(axis, None, None), P(axis, None),
                                 P(axis, None), P(axis, None),
                                 P(axis, None)),
                       out_specs=(state_spec, P(), P()),
                       check_vma=False)

    @partial(jax.jit, donate_argnums=(6,))
    def run(send_ids, eui, ps, pe, pd, mask, pr, inv_deg, base):
        return fn(pr, inv_deg, base, mask, send_ids, eui, ps, pe, pd)

    return jax.tree_util.Partial(run, *_shard_streams(layout, mesh, axis))


def sharded_chunk_stepper(layout: ShardedPNG, mesh: Mesh, axis: str, *,
                          damping: float = 0.85, chunk: int = 8,
                          dangling: str = "none"):
    """Sharded analogue of ``core.pagerank.masked_chunk_stepper``
    (DESIGN.md §7): advances a vertex-sharded (n_pad, B) slot pool by up
    to ``chunk`` iterations in ONE donated dispatch — scatter +
    all-to-all + blocked gather per step, per-column L1 residuals
    psum-combined so each column's freeze decision is replicated on
    device.  Per-column ``tol_col``/``budget`` are replicated data, so
    per-request parameters never retrace; frozen columns are masked out
    of the damping update exactly as in the single-device stepper.

    Returns ``step(pr, base, active, tol_col, budget, inv_deg) ->
    (pr, active, took, res)`` over PADDED sharded ``pr/base/inv_deg``
    and replicated (B,) control arrays.
    """
    if dangling not in ("none", "redistribute"):
        raise ValueError(f"unknown dangling policy {dangling!r}")
    vec = P(axis)
    state_spec = P(axis, None)
    rep = P()

    def local_step(pr, base, active, tol_col, budget, inv_deg, mask_l,
                   send_l, eui_l, ps_l, pe_l, pd_l):
        # pr/base: (shard_size, B); active/tol_col/budget: (B,) replicated
        inv_col = inv_deg[:, None]
        mask_col = mask_l[:, None]
        dang_col = ((inv_deg == 0).astype(pr.dtype) * mask_l)[:, None]
        redist = base * (damping / (1.0 - damping))
        took0 = jnp.zeros(pr.shape[1], dtype=jnp.int32)
        res0 = jnp.full((pr.shape[1],), -1.0, dtype=jnp.float32)
        spmv = _local_gather_spmv(layout, axis, send_l, eui_l, ps_l,
                                  pe_l, pd_l)

        def cond(state):
            i, _, act, _, _ = state
            return (i < chunk) & act.any()

        def body(state):
            i, pr, act, took, res = state
            spr = pr * inv_col
            pr_next = base + damping * spmv(spr)
            if dangling == "redistribute":
                dmass = jax.lax.psum((pr * dang_col).sum(axis=0), axis)
                pr_next = pr_next + dmass[None, :] * redist
            pr_next = pr_next * mask_col
            r = jax.lax.psum(jnp.abs(pr_next - pr).sum(axis=0), axis)
            pr = jnp.where(act[None, :], pr_next, pr)
            res = jnp.where(act, r, res)
            took = took + act.astype(jnp.int32)
            # quarantine guardrail (DESIGN.md §10): the psum residual
            # is replicated, so every shard freezes a NaN/Inf-poisoned
            # column on the same iteration — no extra collective
            act = act & jnp.isfinite(r) & (r >= tol_col) & (took < budget)
            return i + 1, pr, act, took, res

        _, pr, active, took, res = jax.lax.while_loop(
            cond, body, (jnp.int32(0), pr, active, took0, res0))
        return pr, active, took, res

    fn = jax.shard_map(local_step, mesh=mesh,
                       in_specs=(state_spec, state_spec, rep, rep, rep,
                                 vec, vec, P(axis, None, None),
                                 P(axis, None), P(axis, None),
                                 P(axis, None), P(axis, None)),
                       out_specs=(state_spec, rep, rep, rep),
                       check_vma=False)

    @partial(jax.jit, donate_argnums=(6,))
    def step(send_ids, eui, ps, pe, pd, mask, pr, base, active, tol_col,
             budget, inv_deg):
        return fn(pr, base, active, tol_col, budget, inv_deg, mask,
                  send_ids, eui, ps, pe, pd)

    return jax.tree_util.Partial(step, *_shard_streams(layout, mesh, axis))


def _padded_inv_degree(g: Graph, layout: ShardedPNG) -> np.ndarray:
    out_deg = np.asarray(g.out_degree)
    inv = np.where(out_deg == 0, 0.0, 1.0 / np.maximum(out_deg, 1))
    return pad_to_shards(inv.astype(np.float32), layout)


def distributed_pagerank(g: Graph, mesh: Mesh, axis: str, *,
                         num_iterations: int = 20, damping: float = 0.85,
                         tol: float = 0.0, check_every: int = 1,
                         dangling: str = "none",
                         layout: ShardedPNG | None = None,
                         fused_cache: dict | None = None):
    """PageRank over the sharded PCPM engine — one donated fused
    ``lax.while_loop`` dispatch for the whole run (DESIGN.md §6).

    ``fused_cache`` (the plan-level loop cache when called through
    ``pagerank()``/``Session``) memoizes the jitted run per
    hyper-parameter set, so repeated calls skip the shard_map
    re-trace + re-compile exactly like the single-device driver.

    Returns a ``PageRankResult`` (ranks sliced back to ``num_nodes``).
    """
    from .pagerank import PageRankResult   # local: avoids import cycle
    num_shards = int(np.prod([sz for nme, sz in
                              zip(mesh.axis_names, mesh.devices.shape)
                              if nme == axis]))
    layout = layout or build_sharded_png(g, num_shards)
    key = ("sharded_fused", axis, damping, num_iterations, tol,
           check_every, dangling)
    run = fused_cache.get(key) if fused_cache is not None else None
    if run is None:
        run = sharded_power_iteration(layout, mesh, axis,
                                      damping=damping,
                                      num_iterations=num_iterations,
                                      tol=tol, check_every=check_every,
                                      dangling=dangling)
        if fused_cache is not None:
            fused_cache[key] = run
    n = g.num_nodes
    n_pad = layout.padded_nodes
    sharding = NamedSharding(mesh, P(axis))
    pr0_host = np.zeros(n_pad, dtype=np.float32)
    pr0_host[:n] = 1.0 / n
    base_host = np.zeros(n_pad, dtype=np.float32)
    base_host[:n] = (1.0 - damping) / n
    pr0 = jax.device_put(jnp.asarray(pr0_host), sharding)
    inv_deg = jax.device_put(jnp.asarray(_padded_inv_degree(g, layout)),
                             sharding)
    base = jax.device_put(jnp.asarray(base_host), sharding)
    pr, it, res = run(pr0, inv_deg, base)
    it = int(it)
    res_host = np.asarray(res)[:it]
    return PageRankResult(pr[:n], it,
                          [float(r) for r in res_host if r >= 0.0])
