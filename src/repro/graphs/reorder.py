"""Node relabeling for locality (paper §VI-D1, GOrder experiments).

GOrder itself (Wei et al., SIGMOD'16) optimizes a sliding-window score
and is out of scope; we provide the locality knob the paper studies via
two cheaper orderings that move compression ratio r the same direction:

- ``degree_order``:   hub-first labeling (helps skewed graphs)
- ``bfs_order``:      BFS from max-degree seed (clusters neighborhoods)
- ``hybrid_order``:   BFS over a degree-bucketed queue — our default
                      GOrder stand-in; on RMAT graphs it raises r by
                      1.5-2.5x like table V reports for GOrder.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .formats import Graph


def degree_order(g: Graph) -> np.ndarray:
    """perm[old_id] = new_id, descending total degree."""
    rank = np.argsort(-(g.out_degree + g.in_degree), kind="stable")
    perm = np.empty(g.num_nodes, dtype=np.int32)
    perm[rank] = np.arange(g.num_nodes, dtype=np.int32)
    return perm


def bfs_order(g: Graph) -> np.ndarray:
    """BFS labeling over the undirected view, restarting at the
    highest-degree unvisited node (handles disconnected graphs)."""
    return _level_order(g, hub_first=False)


def hybrid_order(g: Graph) -> np.ndarray:
    """Degree-bucketed BFS: BFS traversal, but each frontier is visited
    hub-first so high-degree nodes land near their followers."""
    return _level_order(g, hub_first=True)


# seeds are checked for being reached this many at a time
_SEED_CHUNK = 4096
# threads that sort the neighbour lists, one arc range, then one node
# range each
_MAX_THREADS = 16


def _level_order(g: Graph, *, hub_first: bool) -> np.ndarray:
    """Level-synchronous BFS labeling, one numpy pass per level.

    Seeds are taken in descending total degree (stable), skipping
    visited nodes.  A frontier's nodes get the next labels in frontier
    order (``hub_first``: stably re-sorted by descending degree first).
    Each unvisited neighbour of the frontier goes to the first frontier
    node that reaches it, and the next frontier lists them by that
    node's position, then by id — the order a node-at-a-time BFS over
    sorted, deduplicated neighbour lists gives."""
    n = g.num_nodes
    deg, offsets, indices = _undirected_neighbours(g)
    # the label of the node that reached each node first (a seed: -1;
    # not reached yet: the int32 maximum); labels grow level by level,
    # so a node reached before the current level never changes hands
    unseen = np.iinfo(np.int32).max
    reacher = np.full(n, unseen, dtype=np.int32)
    perm = np.empty(n, dtype=np.int32)
    label = 0
    seeds = np.argsort(-deg, kind="stable")
    linked = int(np.count_nonzero(deg))
    row_lens = np.diff(offsets)
    for lo in range(0, linked, _SEED_CHUNK):
        chunk = seeds[lo:min(lo + _SEED_CHUNK, linked)]
        for seed in chunk[reacher[chunk] == unseen].tolist():
            if reacher[seed] != unseen:
                continue
            reacher[seed] = -1
            frontier = np.array([seed], dtype=np.int32)
            while frontier.size:
                if hub_first:
                    frontier = frontier[np.argsort(-deg[frontier],
                                                   kind="stable")]
                labels = np.arange(label, label + frontier.size,
                                   dtype=np.int32)
                perm[frontier] = labels
                label += frontier.size
                starts = offsets[frontier]
                lens = offsets[frontier + 1] - starts
                # every neighbour entry of the frontier beside the label
                # of its frontier node: on a dense level (an eighth of
                # all entries or more) in node order, from a mask of
                # the frontier's rows; else in frontier order
                dense = lens.sum() > indices.size // 8
                if dense:
                    rows = np.zeros(n, dtype=bool)
                    rows[frontier] = True
                    nbrs = indices[np.repeat(rows, row_lens)]
                    by = np.repeat(perm[rows], row_lens[rows])
                else:
                    by = np.repeat(labels, lens)
                    at = np.repeat(
                        starts - (np.cumsum(lens, dtype=lens.dtype) - lens),
                        lens)
                    at += np.arange(at.size, dtype=at.dtype)
                    nbrs = indices[at]
                np.minimum.at(reacher, nbrs, by)
                # the nodes this level reached, by their reacher's label,
                # then by id: on a dense level from a scan of the nodes;
                # else from the entries, in which each (neighbour,
                # frontier node) pair occurs once
                if dense:
                    fresh = np.flatnonzero((reacher >= labels[0])
                                           & (reacher <= labels[-1]))
                    frontier = fresh[np.argsort(reacher[fresh],
                                                kind="stable")]
                else:
                    frontier = nbrs[reacher[nbrs] == by]
    # nodes on no arc come last in seed order, each its own component
    perm[seeds[linked:]] = np.arange(label, n)
    return perm


# ---------------------------------------------------------------------------
# Registry — what PlanConfig(reorder=...) resolves through (core/plan.py)
# ---------------------------------------------------------------------------
ORDERINGS = {
    "degree": degree_order,
    "bfs": bfs_order,
    "hybrid": hybrid_order,
}


def available_orderings() -> tuple[str, ...]:
    """Every valid ``PlanConfig.reorder`` value (``"none"`` included)."""
    return ("none",) + tuple(sorted(ORDERINGS))


def reorder_permutation(g: Graph, name: str) -> np.ndarray:
    """The ``perm[old_id] = new_id`` permutation for ordering ``name``
    (memoized on the graph instance — a pcpm and a pcpm_pallas plan of
    the same reordered graph compute the BFS once)."""
    if name not in ORDERINGS:
        raise ValueError(f"unknown ordering {name!r}; valid: "
                         f"{available_orderings()}")
    key = f"_reorder_perm_{name}"
    perm = g.__dict__.get(key)
    if perm is None:
        perm = ORDERINGS[name](g).astype(np.int32)
        g.__dict__[key] = perm       # frozen-safe: dict write
    return perm


def inverse_permutation(perm: np.ndarray) -> np.ndarray:
    """``inv[new_id] = old_id`` — maps internal-space vectors/ids back
    to the original labeling (``x_orig = x_int[perm]``,
    ``id_orig = inv[id_int]``)."""
    inv = np.empty(len(perm), dtype=np.int32)
    inv[perm] = np.arange(len(perm), dtype=np.int32)
    return inv


def _undirected_neighbours(g: Graph
                           ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(deg, offsets, indices)``: each node's out-degree plus
    in-degree, and its neighbours over arcs either way, ascending, each
    once; ``offsets`` are int32 where they fit.  A sample sort of packed
    ``(node, neighbour)`` keys on threads (numpy releases the GIL in its
    sorts): each arc range's keys are sorted, then each node range
    merges its slices of every run."""
    n, m = g.num_nodes, g.num_edges
    parts = max(1, min(_MAX_THREADS, os.cpu_count() or 1,
                       (2 * m) >> 22))
    arc_cuts = np.linspace(0, m, parts + 1).astype(np.int64)
    node_cuts = np.linspace(0, n, parts + 1).astype(np.int64)

    def run(i):
        src = g.src[arc_cuts[i]:arc_cuts[i + 1]]
        dst = g.dst[arc_cuts[i]:arc_cuts[i + 1]]
        k = np.empty(2 * src.size, dtype=np.int64)
        for half, a, b in ((k[:src.size], src, dst),
                           (k[src.size:], dst, src)):
            np.left_shift(a, 32, out=half, dtype=np.int64)
            half |= b
        k.sort()
        return k, np.searchsorted(k, node_cuts << 32)

    def part(i):
        lo, hi = node_cuts[i], node_cuts[i + 1]
        k = np.concatenate([r[at[i]:at[i + 1]] for r, at in runs])
        k.sort(kind="stable")                # merges the sorted slices
        firsts = np.arange(lo, hi + 1, dtype=np.int64) << 32
        deg = np.diff(np.searchsorted(k, firsts))
        if k.size:
            k = k[np.concatenate([[True], k[1:] != k[:-1]])]
        return (deg, np.diff(np.searchsorted(k, firsts)),
                k.astype(np.int32))          # the low word: neighbour

    with ThreadPoolExecutor(parts) as pool:
        runs = list(pool.map(run, range(parts)))
        deg, counts, indices = zip(*pool.map(part, range(parts)))
    counts = np.concatenate(counts)
    offsets = np.zeros(n + 1, dtype=np.int32 if counts.sum() < 2 ** 31
                       else np.int64)
    np.cumsum(counts, out=offsets[1:])
    return (np.concatenate(deg).astype(np.int64, copy=False), offsets,
            np.concatenate(indices))
