"""Plan stream accounting (DESIGN.md §14).

``core/comm_model.py`` carries the paper's §V napkin math (eqs. 3-10,
the Table 2 traffic model behind Fig. 8 / Table 6) — a PREDICTION
from (n, m, k, r).  This module counts the same quantity off a built
plan: the arrays one SpMV pass streams, at their real, padded,
on-device sizes.  It is a count from shapes, not a measurement of the
device; predicted and counted land side by side in benchmark
``comm/`` rows.

Accounting rules (full derivation in DESIGN.md §14):

- ``dram`` streams count bytes the paper's model also counts: index
  streams once, value streams per vector column (``ncols`` — the
  multi-vector batch reuses every index stream across B columns, the
  serving stack's amortization story).
- Measured sizes include padding the model ignores: the gather
  schedule's block-padded edge stream ``Mp >= M`` and padded piece
  table.  This is the honest number — padding is traffic.
- ``onchip`` streams are expected to be cache-resident (per-partition
  bins during blocked gather, piece bounds) and are reported
  separately rather than silently dropped or silently added.
- Random-access counters mirror eqs. (8)-(10): we count the
  element-granularity gathers/scatters our implementation issues, the
  measurable analogue of the paper's cache-miss terms.
"""
from __future__ import annotations

import dataclasses

from ..core import comm_model

D_V = 4   # float32 rank values
D_I = 4   # int32 indices


@dataclasses.dataclass(frozen=True)
class CommBreakdown:
    """Bytes one SpMV pass moves, from actual plan array sizes."""

    method: str
    n: int
    m: int
    ncols: int
    dram: dict          # stream name -> bytes/pass (model-comparable)
    onchip: dict        # cache-expected traffic, reported not summed
    gather_ops: int     # element-granularity gathers issued per pass
    scatter_ops: int    # element-granularity scatter-adds per pass

    @property
    def dram_bytes(self) -> int:
        return sum(self.dram.values())

    @property
    def onchip_bytes(self) -> int:
        return sum(self.onchip.values())

    def to_dict(self) -> dict:
        return {"method": self.method, "n": self.n, "m": self.m,
                "ncols": self.ncols, "dram_bytes": self.dram_bytes,
                "onchip_bytes": self.onchip_bytes,
                "dram": dict(self.dram), "onchip": dict(self.onchip),
                "gather_ops": self.gather_ops,
                "scatter_ops": self.scatter_ops}


def measure_plan(plan, ncols: int = 1) -> CommBreakdown:
    """Enumerate the arrays one pass of ``plan``'s SpMV streams.

    Works from the same arrays ``plan_nbytes`` accounts and the
    backends actually bind, so a padded schedule shows up here at its
    padded size.
    """
    n, m = plan.num_nodes, plan.num_edges
    method = plan.config.method
    c = ncols
    dram: dict = {}
    onchip: dict = {}

    if method in ("pcpm", "pcpm_blocked") and plan.png is not None:
        png, sched = plan.png, plan.schedule
        U = int(len(png.update_src))
        if sched is not None:
            Mp = int(len(sched.edge_update_idx_padded))
            P0 = int(len(sched.piece_start))
        else:
            Mp = int(len(png.edge_update_idx))
            P0 = 0
        # Scatter phase: read the update-source list, gather x, write
        # one bin per update; gather phase: stream the (padded) edge->
        # update index list and read each bin back once from DRAM —
        # the expansion to edge granularity hits the per-partition bin
        # working set, which is the paper's cache-residency argument.
        dram["update_src_read"] = U * D_I
        dram["x_gather"] = U * D_V * c
        dram["bins_write"] = U * D_V * c
        dram["bins_read"] = U * D_V * c
        dram["edge_stream_read"] = Mp * D_I
        dram["rank_rw"] = 2 * n * D_V * c
        onchip["bins_expand"] = Mp * D_V * c
        onchip["piece_table"] = 3 * P0 * D_I
        onchip["piece_partials"] = P0 * D_V * c
        gather_ops = U + Mp          # x[update_src] + bins[eui]
        scatter_ops = P0 + n         # piece segment-sum + final rows
    elif method == "pdpr" and plan.csc_src is not None:
        M = int(len(plan.csc_src))
        sched = plan.schedule
        Mp = int(len(sched.edge_update_idx_padded)) if sched is not None else M
        P0 = int(len(sched.piece_start)) if sched is not None else 0
        # Pull: stream src ids, random-gather x per edge (best case one
        # value per access — the model's c_mr*l term is the worst case,
        # reported via vs_model), segment-sum into y.
        dram["src_read"] = M * D_I
        dram["x_gather"] = Mp * D_V * c
        dram["rank_rw"] = 2 * n * D_V * c
        onchip["piece_table"] = 3 * P0 * D_I
        onchip["piece_partials"] = P0 * D_V * c
        gather_ops = Mp
        scatter_ops = P0 + n
    elif method == "bvgas" and plan.bv_src is not None:
        M = int(len(plan.bv_src))
        sched = plan.schedule
        Mp = int(len(sched.edge_update_idx_padded)) if sched is not None else M
        P0 = int(len(sched.piece_start)) if sched is not None else 0
        # Scatter: stream src ids, gather x, write one bin per EDGE
        # (no compression — the r=1 baseline); gather: read every bin
        # back and segment-sum by destination.
        dram["src_read"] = M * D_I
        dram["x_gather"] = M * D_V * c
        dram["bins_write"] = M * D_V * c
        dram["bins_read"] = M * D_V * c
        dram["edge_stream_read"] = Mp * D_I
        dram["rank_rw"] = 2 * n * D_V * c
        onchip["piece_table"] = 3 * P0 * D_I
        onchip["piece_partials"] = P0 * D_V * c
        gather_ops = M + Mp
        scatter_ops = P0 + n
    else:
        raise ValueError(
            f"cannot measure method {method!r}: plan carries none of "
            "png/csc/bv layouts (sharded plans account per-shard; "
            "measure the unsharded base plan)")
    return CommBreakdown(method=method, n=n, m=m, ncols=c, dram=dram,
                         onchip=onchip, gather_ops=gather_ops,
                         scatter_ops=scatter_ops)


def model_params(plan, c_mr: float = 1.0) -> comm_model.ModelParams:
    """Model inputs taken from the plan's MEASURED geometry — k from
    the actual partitioning, r from the built PNG — so prediction and
    measurement disagree only where the model idealizes, not because
    they saw different graphs."""
    part = plan.partitioning
    k = part.num_partitions if part is not None else 1
    try:
        r = float(plan.compression_ratio)
    except Exception:
        r = 1.0
    return comm_model.ModelParams(n=plan.num_nodes, m=plan.num_edges,
                                  k=k, r=max(r, 1e-9), c_mr=c_mr)


_MODEL_FNS = {"pcpm": comm_model.pcpm_bytes,
              "pcpm_blocked": comm_model.pcpm_bytes,
              "pdpr": comm_model.pdpr_bytes,
              "bvgas": comm_model.bvgas_bytes}

_MODEL_KEY = {"pcpm": "pcpm", "pcpm_blocked": "pcpm",
              "pdpr": "pdpr", "bvgas": "bvgas"}


def vs_model(plan, ncols: int = 1) -> dict:
    """Measured-vs-predicted bytes per iteration for one plan — the
    live Fig. 8 row.  ``ratio`` is measured/model at ncols=1 (the
    model is single-vector); the pdpr model is also reported at its
    best case (c_mr = d_v/l) since eq. (3)'s default c_mr=1 is the
    all-miss worst case."""
    meas = measure_plan(plan, ncols=1)
    p = model_params(plan)
    key = _MODEL_KEY[meas.method]
    model_b = float(_MODEL_FNS[meas.method](p))
    out = {
        "method": meas.method,
        "n": meas.n, "m": meas.m, "k": p.k, "r": p.r,
        "measured_bytes_per_iter": meas.dram_bytes,
        "measured_onchip_bytes": meas.onchip_bytes,
        "model_bytes_per_iter": model_b,
        "ratio": meas.dram_bytes / model_b if model_b else float("inf"),
        "measured_gather_ops": meas.gather_ops,
        "measured_scatter_ops": meas.scatter_ops,
        "model_random_accesses": comm_model.random_accesses(p)[key],
    }
    if key == "pdpr":
        best = dataclasses.replace(p, c_mr=p.d_v / p.l)
        out["model_bytes_per_iter_best"] = float(comm_model.pdpr_bytes(best))
    if ncols != 1:
        out["measured_bytes_per_iter_ncols"] = measure_plan(
            plan, ncols=ncols).dram_bytes
        out["ncols"] = ncols
    return out
