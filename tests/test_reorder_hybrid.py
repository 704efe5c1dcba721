"""Locality relabeling on the normal path (paper §VI-D1).

- The level-at-a-time BFS orderings return exactly the permutation of
  the node-at-a-time loops they replace.  The loops below are the plain
  reference: one node of the frontier at a time, its unvisited
  neighbours deduplicated and sorted, marked visited as they are
  reached.  ``hybrid_order`` re-sorts each frontier by descending
  degree first; ``bfs_order`` does not.
- ``Session(reorder="hybrid").pagerank()`` answers in the original ids.
- The relabeling is a build phase with its own span and counter, and
  the plan's r is a counter of the session.
"""
import contextlib

import numpy as np
import pytest

import repro
from repro.core import pagerank_reference
from repro.core import plan as plan_mod
from repro.core.plan import (PlanConfig, build_plan, clear_plan_cache,
                             plan_cache_stats)
from repro.graphs import generators
from repro.graphs.formats import Graph
from repro.graphs.reorder import bfs_order, hybrid_order


def _undirected_csr(g):
    src = np.concatenate([g.src, g.dst])
    dst = np.concatenate([g.dst, g.src])
    order = np.argsort(src, kind="stable")
    offsets = np.zeros(g.num_nodes + 1, dtype=np.int64)
    np.add.at(offsets, src + 1, 1)
    np.cumsum(offsets, out=offsets)
    return offsets, dst[order]


def loop_order(g, *, hub_first):
    n = g.num_nodes
    offsets, indices = _undirected_csr(g)
    deg = (g.out_degree + g.in_degree).astype(np.int64)
    visited = np.zeros(n, dtype=bool)
    perm = np.empty(n, dtype=np.int32)
    label = 0
    for seed in np.argsort(-deg, kind="stable"):
        if visited[seed]:
            continue
        frontier = np.array([seed], dtype=np.int64)
        visited[seed] = True
        while frontier.size:
            if hub_first:
                frontier = frontier[np.argsort(-deg[frontier],
                                               kind="stable")]
            perm[frontier] = np.arange(label, label + frontier.size)
            label += frontier.size
            nxt = []
            for u in frontier:
                nbrs = indices[offsets[u]:offsets[u + 1]]
                fresh = np.unique(nbrs[~visited[nbrs]])
                visited[fresh] = True
                nxt.append(fresh)
            frontier = (np.concatenate(nxt) if nxt
                        else np.array([], dtype=np.int64))
    return perm


def many_components(seed):
    """Hundreds of small components (paths, stars, pairs, isolated
    nodes) with their ids shuffled, plus one larger R-MAT piece."""
    rng = np.random.default_rng(seed)
    big = generators.rmat(8, 4, seed=seed)
    src, dst, base = [big.src], [big.dst], big.num_nodes
    for _ in range(300):
        size = int(rng.integers(1, 7))
        hub = base
        for v in range(1, size):
            a = hub if rng.random() < 0.5 else base + v - 1
            src.append(np.array([a], np.int32))
            dst.append(np.array([base + v], np.int32))
        base += size
    shuffle = rng.permutation(base).astype(np.int32)
    return Graph(base, shuffle[np.concatenate(src)],
                 shuffle[np.concatenate(dst)])


def degree_ties(seed):
    """A ring with chords: every node of the ring has degree 2 or 4,
    so most comparisons of degree tie; arcs listed in a seeded order,
    some twice."""
    rng = np.random.default_rng(seed)
    n = 600
    src = np.concatenate([np.arange(n), np.arange(0, n, 3)])
    dst = np.concatenate([(np.arange(n) + 1) % n,
                          (np.arange(0, n, 3) + n // 2) % n])
    pick = rng.permutation(len(src))
    pick = np.concatenate([pick, pick[:50]])
    return Graph(n, src[pick].astype(np.int32), dst[pick].astype(np.int32))


GRAPHS = {
    "kronecker": lambda: generators.rmat(11, 8, seed=5),
    "kronecker_dense": lambda: generators.rmat(9, 16, seed=17),
    "grid": lambda: generators.grid_2d(23, 31),
    "uniform": lambda: generators.uniform_random(700, 2100, seed=3),
    "many_components": lambda: many_components(7),
    "degree_ties": lambda: degree_ties(11),
    "no_edges": lambda: Graph(5, np.array([], np.int32),
                              np.array([], np.int32)),
}


@pytest.mark.parametrize("hub_first", [True, False],
                         ids=["hybrid", "bfs"])
@pytest.mark.parametrize("shape", sorted(GRAPHS))
def test_level_order_is_the_loops_permutation(shape, hub_first):
    g = GRAPHS[shape]()
    want = loop_order(g, hub_first=hub_first)
    got = (hybrid_order if hub_first else bfs_order)(g)
    np.testing.assert_array_equal(got, want)


def test_arc_order_does_not_move_the_permutation():
    g = generators.rmat(10, 8, seed=2)
    pick = np.random.default_rng(0).permutation(g.num_edges)
    shuffled = Graph(g.num_nodes, g.src[pick], g.dst[pick])
    np.testing.assert_array_equal(hybrid_order(shuffled), hybrid_order(g))


# ---------------------------------------------------------------------------
# The relabeled plan on the normal path
# ---------------------------------------------------------------------------
SOLVE_GRAPHS = {
    "kronecker": lambda: generators.rmat(9, 8, seed=23),
    "uniform": lambda: generators.uniform_random(400, 2400, seed=29),
    "disconnected": lambda: many_components(31),
}


@pytest.mark.parametrize("dangling", ["none", "redistribute"])
@pytest.mark.parametrize("shape", sorted(SOLVE_GRAPHS))
def test_relabeled_session_matches_reference(shape, dangling):
    g = SOLVE_GRAPHS[shape]()
    clear_plan_cache()
    sess = repro.open(g, reorder="hybrid", part_size=64, num_iterations=20,
                      dangling=dangling)
    assert sess.plan.reorder_perm is not None
    res = sess.pagerank()
    want = pagerank_reference(g, num_iterations=20, dangling=dangling)
    assert res.iterations == 20
    np.testing.assert_allclose(np.asarray(res.ranks), want, rtol=2e-4,
                               atol=1e-6)
    clear_plan_cache()


def test_reorder_is_a_build_phase(monkeypatch):
    """``repro.plan.reorder`` runs before the layouts are built, and
    ``reorder_s`` grows only for a reordered build: a plain build and
    a cache hit add nothing."""
    names = []

    @contextlib.contextmanager
    def record(name, *args, **kw):
        names.append(name)
        yield None

    monkeypatch.setattr(plan_mod, "phase", record)
    clear_plan_cache()
    g = generators.rmat(9, 8, seed=3)
    stats = plan_cache_stats()
    build_plan(g, PlanConfig(part_size=64))
    assert "repro.plan.reorder" not in names
    assert stats.reorder_s == 0
    names.clear()
    plan = build_plan(g, PlanConfig(part_size=64, reorder="hybrid"))
    assert names.index("repro.plan.reorder") < names.index("repro.plan.png")
    assert stats.reorder_s > 0
    spent = stats.reorder_s
    assert build_plan(g, PlanConfig(part_size=64, reorder="hybrid")) is plan
    assert stats.reorder_s == spent
    # the relabeled graph the phase made is the plan's internal graph
    gi = plan_mod.internal_graph(g, plan)
    np.testing.assert_array_equal(gi.src, plan.reorder_perm[g.src])
    clear_plan_cache()
    assert plan_cache_stats().reorder_s == 0


@pytest.mark.parametrize("reorder", ["none", "hybrid"])
def test_compression_ratio_counter(reorder):
    """``pcpm_compression_ratio`` is the plan's r, in ``stats()`` and,
    when observing, as a gauge of the metrics registry."""
    clear_plan_cache()
    g = generators.rmat(10, 8, seed=41)
    sess = repro.open(g, reorder=reorder, part_size=64, observe=True)
    r = sess.plan.compression_ratio
    assert r > 1
    assert sess.stats()["pcpm_compression_ratio"] == r
    assert sess.obs.registry.gauge("pcpm_compression_ratio").value == r
    sess.obs.close()
    clear_plan_cache()
