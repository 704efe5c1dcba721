#!/usr/bin/env python3
"""Chip smoke: PCPM PageRank's main path, once, on a TPU.

    python chip_smoke.py              # one chip: batch, serving, Pallas
    python chip_smoke.py --chips 4    # four chips: the sharded path only

One chip, three phases, all through the user entry points
(``repro.open`` -> ``Session.pagerank()`` / ``Session.serve()``):

- batch: LDBC Graphalytics PageRank (damping 0.85, 20 iterations,
  dangling mass redistributed) with ``method="pcpm"`` on graph500-22
  (``rmat(22, 16)``, the Graph500 initiator), checked against a
  float64 scipy power iteration that shares no code with the engine;
- serving: personalized top-10 queries through the continuous-batching
  scheduler on the same plan, forced onto the device stepper, checked
  against a float64 personalized power iteration;
- Pallas: ``method="pcpm_pallas"`` on a smaller graph of the same
  generator, checked against ``pcpm`` and against its program holding
  the compiled kernel (``tpu_custom_call``).

``--chips 4`` runs only ``pcpm_sharded`` over four chips (batch and
sharded serving) against single-device ``pcpm`` on device 0.

The script refuses to run anywhere but a TPU.  Times it prints are
smoke timings of one cold run, not benchmark numbers.  The last line
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import repro  # noqa: E402,F401  (fails here when the repo is not beside)

# Graphalytics PageRank
DAMPING = 0.85
ITERATIONS = 20
# graph500-22: the Graph500 Kronecker generator at scale 22
SCALE = 22
EDGE_FACTOR = 16
# the largest scale whose pcpm_pallas working set fits one v5e (15.5 GB
# by memory_analysis; scale 22 needs about twice that); one pass there
# takes tens of seconds (ROADMAP A4), so it runs one iteration
PALLAS_SCALE = 21
PALLAS_ITERATIONS = 1
# bounds of the checks against the float64 references
RANK_SUM_TOL = 1e-4        # |sum(ranks) - 1|
L1_TOL = 1e-4              # ||ranks - reference||_1
TOP_IDS = 100              # batch: top ids that must agree up to ties
SERVE_TOP = 10             # serving: top-k per query
TIE_RTOL = 1e-5            # scores this close to the cut-off are ties
SCORE_RTOL = 1e-4          # served top-k scores vs the reference
SCORE_ATOL = 1e-8
PALLAS_L1_TOL = 1e-5       # pcpm_pallas vs pcpm on the same graph


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, msg="") -> None:
    """A check that survives ``python -O``."""
    if not ok:
        raise AssertionError(msg)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ------------------------------------------------------- float64 oracle
def transition(g):
    """``(A^T as float64 CSR, 1/outdeg, sink mask)`` built from the raw
    edge list with scipy — no code of the engine under test."""
    import scipy.sparse as sp
    n = g.num_nodes
    at = sp.csr_matrix((np.ones(g.num_edges), (g.dst, g.src)),
                       shape=(n, n))
    outdeg = np.bincount(g.src, minlength=n).astype(np.float64)
    inv = np.divide(1.0, outdeg, out=np.zeros(n), where=outdeg > 0)
    return at, inv, outdeg == 0


def reference_pagerank(oracle, teleport, iters: int) -> np.ndarray:
    """Power iteration from ``teleport`` (n,) or (n, B) columns summing
    to 1: x <- (1-d) t + d (A^T D^-1 x + sink mass * t)."""
    at, inv, sink = oracle
    t = np.asarray(teleport, np.float64)
    t = t / t.sum(axis=0)
    inv = inv if t.ndim == 1 else inv[:, None]
    x = t.copy()
    for _ in range(iters):
        x = (1 - DAMPING) * t + DAMPING * (at @ (x * inv)
                                           + x[sink].sum(axis=0) * t)
    return x


def check_top(ids, ref: np.ndarray, k: int) -> None:
    """``ids`` are the top-``k`` of a candidate; every one must score,
    in ``ref``, at least ``ref``'s k-th best score up to a tie."""
    cut = np.sort(ref)[-k]
    ids = np.asarray(ids)
    require(len(set(ids.tolist())) == k, "duplicate top ids")
    worst = ref[ids].min()
    require(worst >= cut * (1 - TIE_RTOL), (
        f"top-{k} disagrees beyond ties: {worst!r} < {cut!r}"))


def top_ids(x: np.ndarray, k: int) -> np.ndarray:
    return np.argpartition(-x, k - 1)[:k]


# --------------------------------------------------------------- phases
def batch_phase(scale: int, seed: int) -> dict:
    """Graphalytics PageRank with ``pcpm`` at ``scale``; returns what
    the serving phase reuses."""
    import jax
    import repro
    from repro.graphs.generators import rmat
    g, t_gen = timed(lambda: rmat(scale, EDGE_FACTOR, seed=seed))
    log(f"batch: graph500-{scale} n={g.num_nodes} m={g.num_edges} "
        f"generated in {t_gen:.1f}s")
    cfg = repro.EngineConfig(method="pcpm", part_size=65536,
                             damping=DAMPING, num_iterations=ITERATIONS,
                             dangling="redistribute")
    sess, t_plan = timed(lambda: repro.open(g, cfg))
    log(f"batch: plan built in {t_plan:.1f}s (part_size="
        f"{sess.plan.part_size}, r={sess.plan.compression_ratio:.2f})")

    def solve():
        res = sess.pagerank()
        jax.block_until_ready(res.ranks)
        return res

    res, t_first = timed(solve)
    res, t_second = timed(solve)
    require(res.iterations == ITERATIONS, res.iterations)
    log(f"batch: first solve (compile + run) {t_first:.2f}s, second "
        f"solve {t_second:.3f}s [smoke timings, not benchmark numbers]")

    oracle, t_ref = timed(lambda: transition(g))
    ref = reference_pagerank(oracle, np.ones(g.num_nodes), ITERATIONS)
    ranks = np.asarray(res.ranks, np.float64)
    total = ranks.sum()
    l1 = np.abs(ranks - ref).sum()
    require(np.isfinite(ranks).all(), "non-finite ranks")
    require(abs(total - 1.0) <= RANK_SUM_TOL, total)
    require(l1 <= L1_TOL, l1)
    check_top(top_ids(ranks, TOP_IDS), ref, TOP_IDS)
    log(f"batch: ok — sum={total:.8f} L1={l1:.3e} (<= {L1_TOL}) "
        f"top-{TOP_IDS} agree; float64 reference took {t_ref:.1f}s+")
    return {"graph": g, "session": sess, "oracle": oracle}


def serving_phase(state: dict, seed: int, *, queries: int = 8) -> None:
    """Personalized top-10 queries through ``Session.serve`` on the
    batch phase's plan, forced onto the device stepper."""
    g, sess = state["graph"], state["session"]
    rng = np.random.default_rng(seed)
    has_out = np.flatnonzero(np.bincount(g.src, minlength=g.num_nodes))
    nodes = rng.choice(has_out, size=queries, replace=False)
    sch, t_open = timed(lambda: sess.serve(route="stepper", slots=queries))
    seeds = np.zeros((g.num_nodes, queries), np.float32)
    seeds[nodes, np.arange(queries)] = 1.0

    def run():
        uids = [sch.submit(seeds[:, i], top_k=SERVE_TOP, tol=0.0,
                           max_iters=ITERATIONS) for i in range(queries)]
        by = {r.uid: r for r in sch.run_until_drained()}
        return [by[u] for u in uids]

    results, t_serve = timed(run)
    require(sch.trace_count == 1, sch.trace_count)
    ref = reference_pagerank(state["oracle"], seeds, ITERATIONS)
    for i, r in enumerate(results):
        require(r.error is None, r.error)
        require(r.iterations == ITERATIONS, r.iterations)
        check_top(r.top_ids, ref[:, i], SERVE_TOP)
        want = ref[np.asarray(r.top_ids), i]
        err = np.abs(np.asarray(r.top_scores, np.float64) - want)
        require((err <= SCORE_RTOL * want + SCORE_ATOL).all(), (
            i, err.max()))
    log(f"serving: ok — {queries} personalized top-{SERVE_TOP} queries "
        f"(seeds {nodes.tolist()}) served in {t_serve:.2f}s after a "
        f"{t_open:.2f}s stepper compile, trace_count={sch.trace_count} "
        "[smoke timings]")


def pallas_phase(scale: int, seed: int, iters: int) -> None:
    """``pcpm_pallas`` against ``pcpm`` on ``rmat(scale)``."""
    import jax
    import jax.numpy as jnp
    import repro
    from repro.core.pagerank import fused_power_iteration
    from repro.graphs.generators import rmat
    g = rmat(scale, EDGE_FACTOR, seed=seed)
    cfg = repro.EngineConfig(damping=DAMPING, num_iterations=iters,
                             dangling="redistribute")
    want = np.asarray(repro.open(g, cfg.replace(method="pcpm"))
                      .pagerank().ranks, np.float64)
    # the bins of the largest graph that fits take most of the chip:
    # drop every earlier plan's device streams first
    repro.clear_plan_cache()
    sess, t_plan = timed(lambda: repro.open(
        g, cfg.replace(method="pcpm_pallas")))
    packed = sess.engine.plan.blocked
    log(f"pallas: graph500-{scale} n={g.num_nodes} m={g.num_edges} "
        f"part_size={sess.plan.part_size} (from VMEM) partitions="
        f"{packed.update_src.shape[0]} max_updates="
        f"{packed.update_src.shape[1]} max_edges="
        f"{packed.edge_update_local.shape[1]}; plan {t_plan:.1f}s")

    def solve():
        res = sess.pagerank()
        jax.block_until_ready(res.ranks)
        return res

    res, t_first = timed(solve)
    res, t_second = timed(solve)
    got = np.asarray(res.ranks, np.float64)
    l1 = np.abs(got - want).sum()
    require(res.iterations == iters, res.iterations)
    require(l1 <= PALLAS_L1_TOL, l1)
    run = fused_power_iteration(sess.engine, damping=DAMPING,
                                num_iterations=iters, tol=0.0,
                                check_every=1, dangling="redistribute")
    vec = jax.ShapeDtypeStruct((g.num_nodes,), jnp.float32)
    program = run.func.lower(*run.args, vec, vec, vec).as_text()
    compiled = "tpu_custom_call" in program
    if jax.devices()[0].platform == "tpu":
        require(compiled, "pcpm_pallas program holds no compiled kernel")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"pallas: ok — L1 vs pcpm {l1:.3e} (<= {PALLAS_L1_TOL}), "
        f"tpu_custom_call in program: {compiled}; device peak bytes "
        f"{peak}; {iters} iteration(s): "
        f"first {t_first:.2f}s, second {t_second:.3f}s "
        f"({t_second / iters:.3f}s per pass) [smoke timings; the gather "
        "does edges x bin-size work, ROADMAP A4]")


def sharded_phase(scale: int, seed: int, shards: int, *,
                  queries: int = 4) -> None:
    """``pcpm_sharded`` over ``shards`` devices (batch and sharded
    serving) against single-device ``pcpm`` on device 0."""
    import jax
    import repro
    from repro.graphs.generators import rmat
    g, t_gen = timed(lambda: rmat(scale, EDGE_FACTOR, seed=seed))
    log(f"sharded: graph500-{scale} n={g.num_nodes} m={g.num_edges} "
        f"generated in {t_gen:.1f}s")
    cfg = repro.EngineConfig(damping=DAMPING, num_iterations=ITERATIONS,
                             dangling="redistribute")
    one = repro.open(g, cfg.replace(method="pcpm"))
    want = one.pagerank()
    sess, t_plan = timed(lambda: repro.open(
        g, cfg.replace(method="pcpm_sharded", num_shards=shards)))
    log(f"sharded: plan built in {t_plan:.1f}s, wire r="
        f"{sess.plan.compression_ratio:.2f}")

    def solve():
        res = sess.pagerank()
        jax.block_until_ready(res.ranks)
        return res

    res, t_first = timed(solve)
    res, t_second = timed(solve)
    holders = {s.device for s in res.ranks.addressable_shards
               if s.data.size}
    require(len(holders) == shards, (
        f"ranks live on {len(holders)} devices, want {shards}"))
    got = np.asarray(res.ranks, np.float64)
    l1 = np.abs(got - np.asarray(want.ranks, np.float64)).sum()
    require(l1 <= PALLAS_L1_TOL, l1)
    log(f"sharded: batch ok — L1 vs single-device pcpm {l1:.3e}, ranks "
        f"on {len(holders)} devices; first {t_first:.2f}s, second "
        f"{t_second:.3f}s [smoke timings]")

    rng = np.random.default_rng(seed)
    has_out = np.flatnonzero(np.bincount(g.src, minlength=g.num_nodes))
    nodes = rng.choice(has_out, size=queries, replace=False)
    answers = []
    for s in (sess, one):
        sch = s.serve(route="stepper", slots=queries)
        uids = []
        for v in nodes:
            seed_vec = np.zeros(g.num_nodes, np.float32)
            seed_vec[v] = 1.0
            uids.append(sch.submit(seed_vec, top_k=SERVE_TOP, tol=0.0,
                                   max_iters=ITERATIONS))
        by = {r.uid: r for r in sch.run_until_drained()}
        require(sch.trace_count == 1, sch.trace_count)
        answers.append([by[u] for u in uids])
    require(answers[0][0].top_ids is not None)
    for a, b in zip(*answers):
        require(a.error is None and b.error is None, (a.error, b.error))
        scores = np.asarray(b.top_scores, np.float64)
        require(np.allclose(a.top_scores, scores, rtol=SCORE_RTOL), (
            a.top_scores, scores))
        ties = np.isin(a.top_ids, b.top_ids) | np.isclose(
            np.asarray(a.top_scores), scores[-1], rtol=TIE_RTOL)
        require(ties.all(), (a.top_ids, b.top_ids))
    log(f"sharded: serving ok — {queries} personalized top-{SERVE_TOP} "
        "queries match single-device pcpm, trace_count=1")


# ----------------------------------------------------------------- main
def use_compile_cache(jax) -> None:
    """Keep compiled programs where ``JAX_COMPILATION_CACHE_DIR`` says
    (JAX reads it itself), else at one fixed path in the checkout."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {device.platform!r}",
              file=sys.stderr)
        return 2
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": jax.device_count()}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {info['count']}", file=sys.stderr)
        return 2
    use_compile_cache(jax)

    if args.chips == 4:
        sharded_phase(SCALE, args.seed, 4)
    else:
        state = batch_phase(SCALE, args.seed)
        serving_phase(state, args.seed)
        del state
        pallas_phase(PALLAS_SCALE, args.seed, PALLAS_ITERATIONS)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
