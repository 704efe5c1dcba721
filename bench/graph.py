"""Set-up shared by the traffic kinds: the deployment's graph from the
seed, and a session on it through the program's front door."""
from __future__ import annotations

import time

import numpy as np

from bench import kron


def undirected_simple(n: int, src: np.ndarray, dst: np.ndarray):
    """The generated edges in the form LDBC Graphalytics publishes its
    graph500 datasets: undirected, self-loops and duplicate edges
    dropped, isolated vertices removed and the rest renumbered
    ``0 .. n' - 1`` in id order.  Returns ``(n', lo, hi)``, one entry
    per undirected edge, ``lo < hi``, sorted."""
    keep = src != dst
    s = src[keep].astype(np.int64)
    d = dst[keep].astype(np.int64)
    lo, hi = np.divmod(np.unique(np.minimum(s, d) * n + np.maximum(s, d)),
                       n)
    used = np.zeros(n, bool)
    used[lo] = True
    used[hi] = True
    new_id = (np.cumsum(used) - 1).astype(np.int32)
    return int(used.sum()), new_id[lo], new_id[hi]


def both_ways(lo: np.ndarray, hi: np.ndarray, seed: int,
              pieces: int = 256):
    """``(src, dst)``: each undirected edge as its two arcs, listed in
    an order drawn from ``seed`` (the edge list cut into ``pieces``,
    taken in a seeded sequence, each piece's arcs one way, then the
    other)."""
    cuts = np.linspace(0, len(lo), pieces + 1).astype(np.int64)
    order = np.random.default_rng(seed).permutation(pieces)
    parts = [(cuts[p], cuts[p + 1]) for p in order]
    src = np.concatenate([x for a, b in parts for x in (lo[a:b], hi[a:b])])
    dst = np.concatenate([x for a, b in parts for x in (hi[a:b], lo[a:b])])
    return src, dst


def make_graph(config: dict, seed: int, log):
    """The configuration's graph, as a ``repro`` graph: the Graph500
    Kronecker generator's edges (fixed by the configuration's
    ``graph_seed``, made on the device) in Graphalytics' form
    (``undirected_simple``), each edge as both its arcs, listed in an
    order drawn from the run's ``seed``."""
    from repro.graphs import Graph
    if config["form"] != "graphalytics":
        raise ValueError(f"unknown graph form {config['form']!r}")
    a, b, c, _ = config["initiator"]
    t0 = time.perf_counter()
    src, dst = kron.kronecker_edges(
        config["graph_seed"], scale=config["scale"],
        edge_factor=config["edge_factor"], a=a, b=b, c=c,
        permute=config["permute_labels"])
    t_gen = time.perf_counter() - t0
    n, lo, hi = undirected_simple(1 << config["scale"], src, dst)
    del src, dst
    src, dst = both_ways(lo, hi, seed)
    edges = len(lo)
    g = Graph(n, src, dst)
    log(f"graph: scale {config['scale']} graphalytics form: n={n} "
        f"edges={edges} "
        f"arcs={g.num_edges}; generated in {t_gen:.3f} s, "
        f"formed in {time.perf_counter() - t0 - t_gen:.3f} s")
    for key, got in (("vertices", n), ("edges", edges)):
        want = config.get("published", {}).get(key)
        if want:
            log(f"graph: {key} {got} against the published {want} "
                f"({100.0 * (got - want) / want:+.3f} %)")
    return g


def open_session(g, config: dict, log):
    """``repro.open`` with the configuration's PageRank semantics; the
    method and part size are the program's defaults.  Returns the
    session and the seconds the plan build took."""
    import repro
    cfg = repro.EngineConfig(damping=config["damping"],
                             num_iterations=config["iterations"],
                             dangling=config["dangling"])
    t0 = time.perf_counter()
    sess = repro.open(g, cfg)
    plan_s = time.perf_counter() - t0
    log(f"plan: method={sess.plan.method} part_size="
        f"{sess.plan.part_size} built in {plan_s:.3f} s")
    try:
        from repro.obs.comm import measure_plan
        log(f"plan: diagnostic stream bytes per pass (the plan's own "
            f"count, not the roofline's) "
            f"{measure_plan(sess.plan).dram_bytes}")
    except (ImportError, AttributeError) as e:
        log(f"plan: no stream count ({e})")
    return sess, plan_s
