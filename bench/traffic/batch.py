"""Traffic kind ``batch``: back-to-back global PageRank solves.

Each solve is one ``Session.pagerank()`` with the configuration's
semantics, waited for on the device.  Solves run back to back; the
window ends with the solve that crosses ``--seconds``, and ``solve_s``
is the window's length over the solves in it.  Every solve of the
window is compared with the float64 reference.
"""
from __future__ import annotations

import time
import types

import numpy as np

from bench import graph as bench_graph
from bench import oracle


def _solve(state):
    import jax
    with state.span("bench.solve"):
        res = state.sess.pagerank()
        jax.block_until_ready(res.ranks)
    return res


def setup(ctx):
    g = bench_graph.make_graph(ctx.config, ctx.seed, ctx.log)
    sess, plan_s = bench_graph.open_session(g, ctx.config, ctx.log)
    state = types.SimpleNamespace(g=g, sess=sess, plan_s=plan_s,
                                  span=ctx.span, cell=ctx.cell,
                                  config=ctx.config, log=ctx.log)
    with ctx.span("bench.warmup"):
        t0 = time.perf_counter()
        _solve(state)
        ctx.log(f"batch: warm-up solve {time.perf_counter() - t0:.3f} s")
    return state


def window(state, seconds: float) -> dict:
    solves = []
    with state.span("bench.window"):
        t0 = time.perf_counter()
        while True:
            solves.append(_solve(state))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    state.solves = solves
    iterations = sum(r.iterations for r in solves)
    state.log(f"batch: {len(solves)} solves in {elapsed:.4f} s")
    return {"metrics": {"solve_s": elapsed / len(solves)},
            "counters": {"passes": iterations, "columns": 1,
                         "n": state.g.num_nodes, "m": state.g.num_edges,
                         "plan_build_s": state.plan_s}}


def check(state) -> dict:
    """Every solve of the window against the float64 reference."""
    answers = [(np.asarray(r.ranks, np.float64), r.iterations)
               for r in state.solves]
    g, cfg = state.g, state.config
    del state.solves, state.sess          # free the program's state
    t0 = time.perf_counter()
    ref = oracle.pagerank(oracle.transition(g.num_nodes, g.src, g.dst),
                          np.ones(g.num_nodes), damping=cfg["damping"],
                          iterations=cfg["iterations"])
    state.log(f"batch: float64 reference {time.perf_counter() - t0:.3f} s")
    return {"attempted": len(answers), "failed": 0,
            "checks": judge(answers, ref, cfg, state.cell["limits"])}


def judge(answers, ref, cfg, limits) -> list:
    """The numbers compared: the worst L1 distance of a solve's ranks
    from the reference, and the worst miss of the iteration count."""
    l1 = max(float(np.abs(x - ref).sum()) if np.isfinite(x).all()
             else float("inf") for x, _ in answers)
    it = max(abs(i - cfg["iterations"]) for _, i in answers)
    return [{"name": "rank_l1", "value": l1, "limit": limits["rank_l1"]},
            {"name": "iterations_off", "value": it,
             "limit": limits["iterations_off"]}]
