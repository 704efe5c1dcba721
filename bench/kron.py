"""Graph500 Kronecker edge generator on the device, from a seed.

The distribution is the Graph500 reference generator's (the same as
``repro.graphs.generators.rmat``): for each of ``scale`` bits an edge
falls into one quadrant of the adjacency matrix with probabilities
``a``, ``b``, ``c`` and ``1 - a - b - c``; quadrant ``b`` sets the
source's bit, ``c`` the destination's, the last quadrant both.  Vertex
labels are then permuted, so that degree says nothing about an id.
The edge list is the generator's raw output: directed, with
multi-edges and self-loops, over all ``2**scale`` ids
(``bench/graph.py`` brings it into a dataset's form).

Edges are made in ``blocks`` equal pieces by one jitted program, so
the device holds one piece at a time and the generator does not set
the run's peak memory.
"""
from __future__ import annotations

from functools import partial

import numpy as np


def _kron_block(key, perm, block, *, size: int, scale: int, a: float,
                b: float, c: float):
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(key, block)
    ab, abc = a + b, a + b + c

    def bit(i, edges):
        src, dst = edges
        r = jax.random.uniform(jax.random.fold_in(key, i), (size,))
        right = r >= ab                              # destination bit
        down = ((r >= a) & (r < ab)) | (r >= abc)    # source bit
        src = src | (down.astype(jnp.int32) << i)
        dst = dst | (right.astype(jnp.int32) << i)
        return src, dst

    zero = jnp.zeros((size,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    if perm is not None:
        src, dst = perm[src], perm[dst]
    return src, dst


def _key(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits:
    the rest is folded in)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def kronecker_edges(seed: int, *, scale: int, edge_factor: int,
                    a: float, b: float, c: float, permute: bool = True,
                    blocks: int = 16) -> tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` int32 host arrays of the ``edge_factor * 2**scale``
    edges over ``2**scale`` vertices that ``seed`` makes, on the default
    device."""
    import jax
    n = 1 << scale
    m = n * edge_factor
    if m % blocks:
        raise ValueError(f"{m} edges do not split into {blocks} blocks")
    key_edges, key_perm = jax.random.split(_key(seed))
    perm = (jax.jit(partial(jax.random.permutation, x=n))(key_perm)
            .astype(np.int32) if permute else None)
    gen = jax.jit(partial(_kron_block, size=m // blocks, scale=scale,
                          a=a, b=b, c=c))
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    step = m // blocks
    for i in range(blocks):
        s, d = gen(key_edges, perm, np.int32(i))
        src[i * step:(i + 1) * step] = np.asarray(s)
        dst[i * step:(i + 1) * step] = np.asarray(d)
    return src, dst
