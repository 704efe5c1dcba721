"""The dataset's form of the generated graph: Graphalytics' undirected
simple graph with isolated vertices removed, its arcs listed in an
order drawn from the run's seed."""
from __future__ import annotations

import numpy as np
import pytest

from bench import graph

CONFIG = {"scale": 8, "edge_factor": 16,
          "initiator": [0.57, 0.19, 0.19, 0.05], "permute_labels": True,
          "graph_seed": 1, "form": "graphalytics"}


def raw_edges(seed=0, n=50, used=40, m=300):
    """Edges over ids below ``used`` of ``n`` (the rest isolated), with
    self-loops and repeats."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, used, m).astype(np.int32)
    dst = rng.integers(0, used, m).astype(np.int32)
    src[:10] = dst[:10]
    src[10:20], dst[10:20] = dst[20:30], src[20:30]     # reversed repeats
    return n, src, dst


def test_undirected_simple_against_sets():
    n, src, dst = raw_edges()
    n2, lo, hi = graph.undirected_simple(n, src, dst)
    pairs = {(min(u, w), max(u, w)) for u, w in
             zip(src.tolist(), dst.tolist()) if u != w}
    ids = sorted({v for p in pairs for v in p})
    new = {v: i for i, v in enumerate(ids)}
    assert n2 == len(ids) < n
    assert lo.dtype == hi.dtype == np.int32
    assert list(zip(lo.tolist(), hi.tolist())) == sorted(
        (new[u], new[w]) for u, w in pairs)


def test_both_ways_lists_every_edge_once_each_way():
    n, src, dst = raw_edges(1)
    _, lo, hi = graph.undirected_simple(n, src, dst)
    want = sorted(list(zip(lo, hi)) + list(zip(hi, lo)))
    runs = [graph.both_ways(lo, hi, s, pieces=8)
            for s in (2**31 + 5, 2**31 + 5, 2**31 + 6)]
    for s, d in runs:
        assert sorted(zip(s, d)) == want
    np.testing.assert_array_equal(runs[0][0], runs[1][0])
    assert not np.array_equal(runs[0][0], runs[2][0])


def test_graphalytics_form_of_the_generator():
    logs = []
    g = graph.make_graph(CONFIG, 2**31 + 11, logs.append)
    n, src, dst = g.num_nodes, g.src, g.dst
    assert n < 1 << 8
    assert (src != dst).all()
    deg = np.bincount(src, minlength=n)
    assert (deg > 0).all()                            # none isolated
    np.testing.assert_array_equal(deg, np.bincount(dst, minlength=n))
    arcs = set(zip(src.tolist(), dst.tolist()))
    assert len(arcs) == len(src)                      # no repeats
    assert all((w, u) in arcs for u, w in arcs)       # symmetric
    assert any("graphalytics form" in line for line in logs)


def test_seed_orders_the_same_graph():
    a = graph.make_graph(CONFIG, 3, lambda _: None)
    b = graph.make_graph(CONFIG, 4, lambda _: None)
    assert a.num_nodes == b.num_nodes
    assert sorted(zip(a.src, a.dst)) == sorted(zip(b.src, b.dst))
    assert not np.array_equal(a.src, b.src)


def test_unknown_form_is_refused():
    with pytest.raises(ValueError):
        graph.make_graph(dict(CONFIG, form="mystery"), 3, lambda _: None)
