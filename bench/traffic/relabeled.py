"""Traffic kind ``relabeled``: the ``batch`` kind's back-to-back global
PageRank solves, on a session whose plan is built on the graph
relabeled for locality by the configuration's ``reorder``.

The session is opened through ``repro.open`` with the configuration's
semantics and ``reorder``; the method and part size are the program's
defaults.  The window, the check and the judgement are ``batch``'s:
every solve of the window, in the original ids, against the float64
reference over the original arcs.  The window's counters add the
plan's compression ratio r (``compression_r``).
"""
from __future__ import annotations

import hashlib
import time
import types

import numpy as np

from bench import graph as bench_graph
from bench.traffic import batch

check = batch.check


def open_session(g, config: dict, log):
    """``repro.open`` with the configuration's PageRank semantics and
    its relabeling.  Returns the session and the seconds the plan build
    took, the relabeling included."""
    import repro
    cfg = repro.EngineConfig(damping=config["damping"],
                             num_iterations=config["iterations"],
                             dangling=config["dangling"],
                             reorder=config["reorder"])
    t0 = time.perf_counter()
    sess = repro.open(g, cfg)
    plan_s = time.perf_counter() - t0
    plan = sess.plan
    digest = hashlib.sha256(
        np.ascontiguousarray(plan.reorder_perm, np.int32)).hexdigest()
    log(f"plan: method={plan.method} part_size={plan.part_size} "
        f"reorder={plan.config.reorder} built in {plan_s:.3f} s; "
        f"r={plan.compression_ratio:.6f} updates="
        f"{g.num_edges / plan.compression_ratio:.0f}; permutation "
        f"sha256 {digest}")
    return sess, plan_s


def setup(ctx):
    g = bench_graph.make_graph(ctx.config, ctx.seed, ctx.log)
    sess, plan_s = open_session(g, ctx.config, ctx.log)
    state = types.SimpleNamespace(g=g, sess=sess, plan_s=plan_s,
                                  span=ctx.span, cell=ctx.cell,
                                  config=ctx.config, log=ctx.log)
    with ctx.span("bench.warmup"):
        t0 = time.perf_counter()
        batch._solve(state)
        ctx.log(f"relabeled: warm-up solve {time.perf_counter() - t0:.3f} s")
    stats = sess.stats()
    ctx.log(f"relabeled: expand {stats.get('expand')} window_rows "
            f"{getattr(sess.plan.schedule, 'window_rows', None)}; "
            f"plan phases {stats['plan_cache']}")
    return state


def window(state, seconds: float) -> dict:
    win = batch.window(state, seconds)
    win["counters"]["compression_r"] = state.sess.plan.compression_ratio
    return win
