"""The front door: ``repro.open(g, EngineConfig(...))`` (DESIGN.md §8).

One ``EngineConfig`` unifies the method / part_size / num_shards /
damping / tol / iters / dangling / slots knobs that used to be
duplicated across four constructors (``SpMVEngine``, ``pagerank()``,
``PageRankServer``, ``SlotScheduler``).  A ``Session`` resolves the
graph's ``GraphPlan`` ONCE through the process-level plan cache and
serves every workload from it:

    sess = repro.open(g, repro.EngineConfig(method="pcpm"))
    res  = sess.pagerank()                  # fused while_loop driver
    y    = sess.spmv(x)                     # one A^T x pass
    sch  = sess.serve()                     # continuous-batching pool
    srv  = sess.server(batch=8)             # AOT lockstep batch server
    sess.plan.save("web.plan.npz")          # persist the preprocessing

Dynamic graphs (DESIGN.md §9): a session is a live handle, not a
snapshot —

    sess.apply_delta(GraphDelta.insert(edges))   # incremental plan patch
    res = sess.pagerank(warm=True)               # residual-push update

``apply_delta`` patches the plan for the delta's dirty partitions only
(stream/patch.py) and ``warm=True`` pushes the residual seeded at the
changed edges' endpoints instead of re-running full power iteration
(stream/incremental.py).

The old entry points keep working as thin shims over the same plan
cache and backend registry, so both paths stay test-covered.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax.numpy as jnp

from .core.pagerank import PageRankResult, pagerank
from .core.plan import (DEFAULT_GATHER_BLOCK, GraphPlan, PlanConfig,
                        build_plan)
from .core.spmv import SpMVEngine
from .graphs.formats import Graph
from .obs.trace import phase


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Every knob of the plan AND run layers in one hashable value.

    Plan-layer fields (select the ``GraphPlan``): ``method``,
    ``part_size``, ``num_shards``, ``gather_block``.
    Run-layer fields are the iteration/serving defaults a ``Session``
    applies; each method accepts per-call overrides.
    """
    # plan layer
    method: str = "pcpm"
    part_size: Optional[int] = None       # None: the backend derives it
    num_shards: Optional[int] = None      # sharding backends; None = all
    gather_block: int = DEFAULT_GATHER_BLOCK
    two_phase: bool = False               # rejected by Session (fused)
    # locality-enhancing node relabeling (paper §VI-D1): "none",
    # "degree", "bfs" or "hybrid" — the plan's layouts are built on the
    # relabeled graph; every Session/serve result is mapped back to the
    # original ids transparently
    reorder: str = "none"
    # run layer: iteration
    damping: float = 0.85
    num_iterations: int = 20
    tol: float = 0.0
    check_every: int = 1
    dangling: str = "none"
    # run layer: serving
    slots: int = 4
    chunk: int = 8
    # observability (DESIGN.md §14): OFF by default — when True the
    # session owns an ``obs.Observability`` bundle (span tracer +
    # flight recorder + metrics registry) and every workload it fans
    # out reports through it
    observe: bool = False

    def plan_config(self) -> PlanConfig:
        return PlanConfig(method=self.method, part_size=self.part_size,
                          num_shards=self.num_shards,
                          gather_block=self.gather_block,
                          reorder=self.reorder)

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)


class Session:
    """One graph, one plan, every workload.

    Construction resolves (or builds, exactly once per process) the
    ``GraphPlan`` for ``(g, config)``; ``pagerank``/``spmv``/``serve``/
    ``server`` all run from that single plan — the build count stays 1
    no matter how many workloads the session fans out (asserted in
    tests/test_api.py).
    """

    def __init__(self, g: Graph, config: EngineConfig | None = None,
                 *, idmap=None, **overrides):
        cfg = config or EngineConfig()
        if overrides:
            cfg = cfg.replace(**overrides)
        if cfg.two_phase:
            raise ValueError(
                "two_phase=True cannot be combined with the Session's "
                "fused consumers (pagerank/serve run under jit, where "
                "the host-side phase barrier does not exist); build a "
                "two-phase SpMVEngine directly for phase timing.")
        self.graph = g
        self.config = cfg
        # external-id mapping for ingested real graphs (ingest/
        # idmap.py) — threaded through to serve results and
        # ``top_ranked``; None for synthetic dense-id graphs
        self.idmap = idmap
        # observability bundle (DESIGN.md §14) — None until
        # ``observe()`` is called or ``cfg.observe`` asks for it
        self._obs = None
        if cfg.observe:
            self.observe()
        # build_plan validates the graph at entry (crisp ValueError on
        # out-of-range ids / bad dtypes, DESIGN.md §10)
        self.plan: GraphPlan = build_plan(g, cfg.plan_config())
        self.engine = SpMVEngine(g, plan=self.plan)
        # warm-start state (DESIGN.md §9): the graph and ranks of the
        # last solve, the L1 step-residual it achieved, and the
        # concatenated deltas applied since
        self._solved_graph = None
        self._solved_ranks = None
        self._solved_key = None            # (damping, dangling)
        self._solved_res = np.inf
        self._delta_acc = None

    # --------------------------------------------------- observability
    def observe(self, *, capacity: int = 8192, dump_dir=None):
        """Attach (or return) this session's ``Observability`` bundle
        (DESIGN.md §14).  Idempotent: the first call creates the
        bundle — span tracer over a bounded flight recorder and typed
        metrics registry — and every later call returns the same one.
        Handles created AFTER the bundle exists (``serve()``/
        ``gateway()``) report through it; ``pagerank``/``apply_delta``
        on this session do too."""
        if self._obs is None:
            from .obs import Observability
            self._obs = Observability(capacity=capacity,
                                      dump_dir=dump_dir)
        return self._obs

    @property
    def obs(self):
        """The session's ``Observability`` bundle, or None when
        observation was never requested."""
        return self._obs

    def stats(self) -> dict:
        """One dict joining every cache/observability surface the
        session can see: process-level plan-cache counters (with the
        cumulative seconds of each build phase), the plan's compression
        ratio r, and — when observing — the metrics registry and
        flight-recorder occupancy."""
        from .core.plan import plan_cache_stats
        out = {"plan_cache": dataclasses.asdict(plan_cache_stats()),
               "pcpm_compression_ratio": self._compression_ratio(),
               "method": self.config.method,
               "n": self.plan.num_nodes, "m": self.plan.num_edges}
        expand = self._expand_stats()
        if expand is not None:
            out["expand"] = expand
        if self._obs is not None:
            out["obs"] = self._obs.stats()
        return out

    def _gauges(self, values: dict, help: str) -> None:
        """Report ``values`` as gauges of the metrics registry, when
        observing."""
        if self._obs is not None:
            for name, value in values.items():
                self._obs.registry.gauge(name, help).set(value)

    def _compression_ratio(self) -> float:
        """The plan's r, arcs per PCPM update (paper table V;
        ``GraphPlan.compression_ratio``), also a gauge when
        observing."""
        r = self.plan.compression_ratio
        self._gauges({"pcpm_compression_ratio": r},
                     "arcs per pcpm update of the plan")
        return r

    def _expand_stats(self):
        """The pcpm expand's counters (``backends.expand_stats``), or
        None for other methods; reported into the metrics registry as
        gauges when observing."""
        if self.plan.method != "pcpm":
            return None
        from .core.backends import expand_stats
        stats = expand_stats(self.plan)
        self._gauges(stats, "pcpm expand, per pass of one rank column")
        return stats

    # ---------------------------------------------------------- deltas
    def apply_delta(self, delta) -> "Session":
        """Advance the session's graph by one edge-delta batch: the
        plan is patched incrementally (dirty partitions only, full
        rebuild past the dirtiness threshold — stream/patch.py) and
        the engine rebound to it.  Accumulates warm-start state so a
        following ``pagerank(warm=True)`` costs a residual push, not a
        full power iteration.  Serving handles created before the
        delta keep running on the old plan; call their
        ``apply_delta``/construct new ones for the updated graph."""
        from .stream.delta import apply_delta as apply_edges
        from .stream.patch import patch_plan
        sp = (self._obs.tracer.start("session_delta", trace="plan",
                                     adds=len(delta.add_src),
                                     removes=len(delta.rem_src))
              if self._obs is not None else None)
        try:
            g_new = apply_edges(self.graph, delta)
            self.plan = patch_plan(self.plan, delta, g_new)
        except Exception as e:
            if sp is not None:
                sp.end(status="error", error=repr(e))
            raise
        self.graph = g_new
        self.engine = SpMVEngine(g_new, plan=self.plan)
        if sp is not None:
            sp.end(n=g_new.num_nodes, m=int(g_new.src.shape[0]))
        if self._solved_graph is not None:
            self._delta_acc = (delta if self._delta_acc is None
                               else self._delta_acc + delta)
        return self

    # ------------------------------------------------------------- run
    def spmv(self, x) -> jnp.ndarray:
        """One y = A^T x pass ((n,) or (n, d)) on the plan's backend."""
        return self.engine(jnp.asarray(x))

    def pagerank(self, *, warm: bool = False,
                 **overrides) -> PageRankResult:
        """Run the fused power iteration with the session defaults;
        keyword overrides (num_iterations/tol/damping/check_every/
        dangling/driver) apply per call.

        ``warm=True`` after ``apply_delta`` patches the PREVIOUS
        result through the residual-push driver (seeded only at the
        changed edges' endpoints) instead of iterating from scratch.
        The sparse seed is only exact when the stored ranks are a
        converged fixed point of the old graph, so the warm path runs
        iff the previous solve achieved an L1 step-residual <= this
        call's ``tol`` (and damping/dangling match); otherwise it
        falls back to a cold run rather than silently under-deliver
        accuracy.  ``tol`` and ``num_iterations`` mean exactly what
        they mean cold: same stopping rule, ``num_iterations`` bounds
        the push sweeps.  Either way the result is stored as the next
        warm-start point."""
        cfg = self.config
        kw = dict(num_iterations=cfg.num_iterations, damping=cfg.damping,
                  tol=cfg.tol, check_every=cfg.check_every,
                  dangling=cfg.dangling)
        kw.update(overrides)
        key = (kw["damping"], kw["dangling"])
        tol, budget = kw["tol"], kw["num_iterations"]
        # reordered plans warm-start too: update_ranks composes the
        # stored original-space ranks through ``reorder_perm`` into the
        # plan's internal space and gathers the result back, so only
        # the labeling differs — the honest fallback below remains for
        # unconverged/mismatched state, never for reordering alone
        warm_hit = (warm and self._solved_ranks is not None
                    and self._solved_key == key
                    and 0.0 < tol and self._solved_res <= tol)
        tracer = self._obs.tracer if self._obs is not None else None
        with phase("repro.solve", tracer, trace="plan",
                   method=self.config.method, warm=bool(warm_hit),
                   n=self.plan.num_nodes) as sp:
            if warm_hit:
                from .stream.delta import GraphDelta
                from .stream.incremental import update_ranks
                res = update_ranks(
                    self.plan, self._delta_acc or GraphDelta.of(),
                    self._solved_ranks, g_old=self._solved_graph,
                    g_new=self.graph, damping=kw["damping"],
                    dangling=kw["dangling"], tol=tol, max_push=budget)
            else:
                res = pagerank(self.graph, engine=self.engine, **kw)
                if self._obs is not None:
                    self._compression_ratio()
                    self._expand_stats()
            if sp is not None:
                sp.annotate(iterations=res.iterations, residual=float(
                    (res.residuals or [np.inf])[-1]))
        achieved = (res.residuals or [np.inf])[-1]
        self._solved_graph = self.graph
        self._solved_ranks = res.ranks
        self._solved_key = key
        self._solved_res = float(achieved)
        self._delta_acc = None
        return res

    def top_ranked(self, k: int = 10):
        """``(ids, scores)`` of the ``k`` highest-ranked nodes from the
        last ``pagerank()`` solve; ids are the graph's EXTERNAL labels
        when the session carries a ``NodeIdMapping`` (ingested real
        graphs), original dense ids otherwise."""
        if self._solved_ranks is None:
            raise ValueError("no solve yet: run pagerank() first")
        ranks = np.asarray(self._solved_ranks)
        k = min(int(k), ranks.shape[0])
        part = np.argpartition(-ranks, k - 1)[:k]
        ids = part[np.lexsort((part, -ranks[part]))]   # score desc, id asc
        scores = ranks[ids]
        if self.idmap is not None:
            return self.idmap.to_external(ids), scores
        return ids.astype(np.int64), scores

    # ----------------------------------------------------- checkpoints
    def save_checkpoint(self, path: str) -> None:
        """Persist the last solve as a fingerprint-stamped rank
        checkpoint (reliability/snapshot.py) — what a restarted
        process hands to ``load_checkpoint`` to warm-start instead of
        recomputing.  Requires a prior ``pagerank()`` on this
        session."""
        if self._solved_ranks is None:
            raise ValueError("nothing to checkpoint: run pagerank() "
                             "first")
        from .reliability.snapshot import save_rank_checkpoint
        save_rank_checkpoint(
            path, self._solved_graph, np.asarray(self._solved_ranks),
            residual=self._solved_res, damping=self._solved_key[0],
            dangling=self._solved_key[1])

    def load_checkpoint(self, path: str, *, g_old: Graph | None = None,
                        delta=None) -> "Session":
        """Warm-start this session from a rank checkpoint.

        - Checkpoint fingerprint == this session's graph: the ranks
          become the warm state directly — the next
          ``pagerank(warm=True)`` is (near-)free.
        - Checkpoint taken on ``g_old`` with ``delta`` applied since
          (the restart-across-a-delta-chain case): pass both.  The
          lineage is PROVEN by fingerprints — ``g_old`` must hash to
          the checkpoint's fingerprint and ``g_old + delta`` to this
          session's graph — then ``pagerank(warm=True)`` routes
          through the residual-push updater (stream/incremental.py)
          instead of a cold solve.
        - Anything else: crisp ``ValueError``; a checkpoint for the
          wrong graph must never silently seed answers."""
        from .core.plan import graph_fingerprint
        from .reliability.snapshot import load_rank_checkpoint
        ckpt = load_rank_checkpoint(path)
        fp_here = graph_fingerprint(self.graph)
        if ckpt.graph_fp == fp_here:
            self._solved_graph = self.graph
            self._delta_acc = None
        elif g_old is not None and delta is not None:
            from .stream.delta import shifted_fingerprint
            if graph_fingerprint(g_old) != ckpt.graph_fp:
                raise ValueError(
                    "checkpoint mismatch: g_old does not hash to the "
                    "checkpoint's graph fingerprint "
                    f"({ckpt.graph_fp[:12]}…)")
            if shifted_fingerprint(ckpt.graph_fp, delta) != fp_here:
                raise ValueError(
                    "checkpoint mismatch: g_old + delta is not this "
                    "session's graph (shifted fingerprint differs) — "
                    "the delta chain does not connect the checkpoint "
                    "to the current graph")
            self._solved_graph = g_old
            self._delta_acc = delta
        else:
            raise ValueError(
                "checkpoint is for a different graph (fingerprint "
                f"{ckpt.graph_fp[:12]}… != {fp_here[:12]}…); pass "
                "g_old= and delta= to warm-start across a delta chain")
        self._solved_ranks = jnp.asarray(ckpt.ranks)
        self._solved_key = (ckpt.damping, ckpt.dangling)
        self._solved_res = float(ckpt.residual)
        return self

    def serve(self, *, route: str = "auto", **overrides):
        """A continuous-batching ``SlotScheduler`` sharing this
        session's plan (and compiled device streams).  ``route``
        picks the personalized-query path (DESIGN.md §11):
        ``"auto"`` sends loose-tolerance top-k queries through the
        forward-push backend and the rest to the masked stepper,
        ``"push"``/``"stepper"`` force one side for every query."""
        from .serve.scheduler import SlotScheduler
        cfg = self.config
        kw = dict(slots=cfg.slots, damping=cfg.damping, chunk=cfg.chunk,
                  dangling=cfg.dangling, route=route, idmap=self.idmap,
                  obs=self._obs)
        kw.update(overrides)
        return SlotScheduler(self.graph, engine=self.engine, **kw)

    def gateway(self, *, config=None, autotune: bool = True,
                **overrides):
        """An async serving front door over this session's plan
        (DESIGN.md §13): a dedicated device thread steps the slot
        pool, a worker pool answers push-eligible queries inline, and
        ``submit()`` returns a future immediately with a warm-result
        LRU serving repeats in O(k).

        ``autotune=True`` (default) probes the engine's measured
        multi-vector SpMV cost and sizes the slot pool against
        ``config.target_chunk_s`` instead of the session's static
        ``slots``; an explicit ``slots=`` override always wins.  The
        chosen size and the probe curve are attached as
        ``gateway.autotune_report``."""
        from .gateway import Gateway, GatewayConfig, autotune_slots
        cfg = config or GatewayConfig()
        report = None
        if autotune and "slots" not in overrides:
            report = autotune_slots(
                self.engine, chunk=overrides.get("chunk",
                                                 self.config.chunk),
                target_chunk_s=cfg.target_chunk_s,
                candidates=cfg.autotune_candidates,
                default=self.config.slots)
            overrides["slots"] = report.chosen
        sch = self.serve(**overrides)
        gw = Gateway(sch, config=cfg)
        gw.autotune_report = report
        return gw

    def server(self, *, batch: int = 1, **overrides):
        """An AOT-compiled lockstep ``PageRankServer`` sharing this
        session's plan (batched personalized queries)."""
        from .serve.engine import PageRankServer
        cfg = self.config
        kw = dict(damping=cfg.damping, num_iterations=cfg.num_iterations,
                  tol=cfg.tol, check_every=cfg.check_every,
                  dangling=cfg.dangling)
        kw.update(overrides)
        return PageRankServer(self.graph, engine=self.engine,
                              batch=batch, **kw)


def open(g: Graph, config: EngineConfig | None = None, *,
         idmap=None, **overrides) -> Session:
    """Open a :class:`Session` on ``g`` — the public front door.
    ``overrides`` are ``EngineConfig`` fields applied on top of
    ``config`` (or the defaults): ``repro.open(g, method="pdpr")``.
    ``idmap`` attaches a ``NodeIdMapping`` (ingest/idmap.py) so serve
    and ``top_ranked`` results carry the graph's external ids."""
    return Session(g, config, idmap=idmap, **overrides)
