"""The plain reference: PageRank in float64 with scipy, from the raw
edge list, sharing no code with the program under test; and its
control, the same iteration with the ranks stored in bfloat16.

Semantics (LDBC Graphalytics PageRank): from the teleport
distribution ``t`` (uniform, or one column per personalized query),
``iterations`` times ``x <- (1-d) t + d (A^T D^-1 x + sink mass * t)``,
so the mass parked on vertices without out-edges is redistributed over
``t`` and the total stays 1.
"""
from __future__ import annotations

import numpy as np


def transition(n: int, src: np.ndarray, dst: np.ndarray):
    """``(A^T as float64 CSR, 1/outdeg, sink mask)``."""
    import scipy.sparse as sp
    at = sp.csr_matrix((np.ones(len(src)), (dst, src)), shape=(n, n))
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.divide(1.0, outdeg, out=np.zeros(n), where=outdeg > 0)
    return at, inv, outdeg == 0


def pagerank(oracle, teleport: np.ndarray, *, damping: float,
             iterations: int) -> np.ndarray:
    """Power iteration from ``teleport`` (n,) or (n, B), each column
    normalized to sum 1."""
    at, inv, sink = oracle
    t = np.asarray(teleport, np.float64)
    t = t / t.sum(axis=0)
    inv = inv if t.ndim == 1 else inv[:, None]
    x = t.copy()
    for _ in range(iterations):
        # (1-d) t + d (A^T D^-1 x + mass t), with fewer temporaries
        y = at @ (x * inv)
        y *= damping
        y += t * ((1 - damping) + damping * x[sink].sum(axis=0))
        x = y
    return x


def pagerank_bf16(n: int, src: np.ndarray, dst: np.ndarray,
                  teleport: np.ndarray, *, damping: float,
                  iterations: int) -> np.ndarray:
    """The control: the reference's iteration on the default JAX
    device with the ranks stored in bfloat16 between iterations (sums
    accumulate in float32) — the step below the float32 the
    configurations state.  Returns float64 ranks like ``pagerank``."""
    import jax
    import jax.numpy as jnp
    t = np.asarray(teleport, np.float64)
    t = (t / t.sum(axis=0)).astype(np.float32)
    outdeg = np.bincount(src, minlength=n)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1), 0.0)
    inv = inv.astype(np.float32)
    if t.ndim == 2:
        inv = inv[:, None]

    @jax.jit
    def run(src, dst, t, inv):
        sink = (inv == 0).astype(jnp.float32)

        def body(_, x):
            x = x.astype(jnp.float32)
            y = jax.ops.segment_sum((x * inv)[src], dst, num_segments=n)
            mass = (x * sink).sum(axis=0)
            x = (1 - damping) * t + damping * (y + mass * t)
            return x.astype(jnp.bfloat16)

        return jax.lax.fori_loop(0, iterations, body,
                                 t.astype(jnp.bfloat16))

    x = run(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(t),
            jnp.asarray(inv))
    return np.asarray(x.astype(jnp.float32), np.float64)

