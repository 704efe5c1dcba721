"""The donated residual-push while_loop — shared home (DESIGN.md §9/§11).

One device loop, two callers with different seedings:

- **Delta push** (stream/incremental.py): ``r0`` is the sparse
  residual of a graph delta over a converged prior — a warm start.
- **Query push** (serve/push.py): ``pr0 = seed`` and ``r0 = x1 - x0``,
  the first power-iteration step from the seed — so the push iterates
  are EXACTLY the masked chunk stepper's iterates for the same query
  (same x0, same operator), and its stopping rule ``‖r‖₁ < tol`` is
  the stepper's per-step L1-change rule.  Equal tolerances mean equal
  stopping accuracy (final L1 distance to the fixed point
  ≤ tol·d/(1−d) either way).

The loop is ONE donated jitted ``lax.while_loop`` over the plan's
``spmv_fn``; pcpm plans route through the arg-passing ``_pcpm_push``
whose jit cache keys on bucket-padded stream SHAPES, so a stream of
patched plans — and every per-seed query — reuses one compiled
executable.  ``tol``/``max_push`` are runtime data: one trace serves
every tolerance.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .backends import fused_loop_cache, spmv_fn
from .plan import GraphPlan

# residuals ring size; ``max_push`` is runtime data clamped to this,
# so changing it (or tol) NEVER retraces the push loop
MAX_PUSH_BUF = 400

# shape buckets for the arg-passing pcpm push path: stream lengths are
# rounded up with inert pads to a multiple of max(PUSH_PAD, ~3-6% of
# the length), so consecutive small deltas (whose true lengths wobble
# by O(|delta|)) land in the SAME bucket and reuse one compiled
# executable — zero compile per delta.  A delta that outgrows its
# bucket costs one retrace, nothing else.
PUSH_PAD = 4096


def _bucket(length: int, *, align: int = 1) -> int:
    mult = max(PUSH_PAD, 1 << max(int(length).bit_length() - 5, 0))
    tgt = -(-max(length, 1) // mult) * mult
    return -(-tgt // align) * align


def _pad_to(arr: np.ndarray, fill, *, align: int = 1) -> np.ndarray:
    tgt = _bucket(len(arr), align=align)
    if tgt == len(arr):
        return arr
    out = np.full(tgt, fill, dtype=arr.dtype)
    out[:len(arr)] = arr
    return out


def _pcpm_push_streams(plan: GraphPlan):
    """Bucket-padded device copies of the pcpm streams for the
    arg-passing push loop (cached on the plan).

    Pads are inert by the same sentinel scheme the gather schedule
    already uses: pad pieces have start=end=0 and the ``num_nodes``
    destination (their contribution lands in the dropped overflow
    segment), pad pointer entries reference update 0 but belong to no
    piece, pad updates are referenced by no edge."""
    dev = plan._device.get("push_streams")
    if dev is None:
        s = plan.schedule
        n = plan.num_nodes
        blk = s.block
        dev = (jnp.asarray(_pad_to(plan.png.update_src, 0)),
               jnp.asarray(_pad_to(s.edge_update_idx_padded, 0,
                                   align=blk)),
               jnp.asarray(_pad_to(s.piece_start, 0)),
               jnp.asarray(_pad_to(s.piece_end, 0)),
               jnp.asarray(_pad_to(s.piece_dst, n)))
        plan._device["push_streams"] = dev
    return dev


def _push_while(pr, r, inv_deg, tol, max_push, spmv, *, num_nodes: int,
                damping: float, dangling: str):
    """THE push loop body — single home of the stopping rule, residual
    ring and dangling handling, shared by the arg-passing pcpm path
    and the generic closure path (``spmv`` is any traceable
    ``x -> AᵀD⁻¹-applied x``)."""
    dang = (inv_deg == 0).astype(pr.dtype)
    residuals0 = jnp.full((MAX_PUSH_BUF,), -1.0, dtype=jnp.float32)

    def cond(state):
        it, _, r, _ = state
        return ((it < jnp.minimum(max_push, MAX_PUSH_BUF))
                & (jnp.abs(r).sum() >= tol))

    def body(state):
        it, pr, r, residuals = state
        residuals = residuals.at[it].set(jnp.abs(r).sum())
        pr = pr + r
        r_next = damping * spmv(r * inv_deg)
        if dangling == "redistribute":
            r_next = r_next + (r * dang).sum() * (damping / num_nodes)
        return it + 1, pr, r_next, residuals

    it, pr, r, residuals = jax.lax.while_loop(
        cond, body, (jnp.int32(0), pr, r, residuals0))
    return pr, it, residuals, r


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("num_nodes", "block", "damping", "dangling"))
def _pcpm_push(pr, r, inv_deg, tol, max_push, upd_src, eui, ps, pe, pd,
               *, num_nodes: int, block: int, damping: float,
               dangling: str):
    """Module-level push loop with the streams as ARGUMENTS: the jit
    cache keys on their (bucketed) shapes, not their contents, so a
    stream of patched plans shares one compiled loop."""
    from .spmv import pcpm_gather_blocked

    def spmv(x):
        return pcpm_gather_blocked(x[upd_src], eui, ps, pe, pd,
                                   num_nodes=num_nodes, block=block)

    return _push_while(pr, r, inv_deg, tol, max_push, spmv,
                       num_nodes=num_nodes, damping=damping,
                       dangling=dangling)


def residual_push_loop(plan: GraphPlan, *, damping: float = 0.85,
                       dangling: str = "none"):
    """The plan's jitted push loop: ``run(pr, r, inv_deg, tol,
    max_push) -> (pr, sweeps, residuals, r_out)`` with ``pr`` and
    ``r`` donated; ``residuals`` is a (MAX_PUSH_BUF,) device array of
    the per-sweep pre-push ‖r‖₁ (−1.0 in unused slots) and ``r_out``
    the remaining residual vector (its norm is < tol iff the loop
    converged; ``update_ranks`` re-invokes with it when a budget
    larger than MAX_PUSH_BUF has sweeps left).  ``tol``/``max_push``
    are runtime data — one trace serves every tolerance.

    pcpm plans route through the arg-passing ``_pcpm_push`` (compiled
    once per shape bucket per process); other backends get a per-plan
    closure loop over their ``spmv_fn`` (compiled once per plan)."""
    if dangling not in ("none", "redistribute"):
        raise ValueError(f"unknown dangling policy {dangling!r}")
    key = ("push", damping, dangling)
    cache = fused_loop_cache(plan)
    cached = cache.get(key)
    if cached is not None:
        return cached

    if plan.method == "pcpm":
        streams = _pcpm_push_streams(plan)
        n, blk = plan.num_nodes, plan.schedule.block

        def run(pr, r, inv_deg, tol, max_push):
            return _pcpm_push(pr, r, inv_deg,
                              jnp.float32(tol), jnp.int32(max_push),
                              *streams, num_nodes=n, block=blk,
                              damping=damping, dangling=dangling)
    else:
        n = plan.num_nodes

        # the plan's streams enter as an argument (core/backends.py)
        @partial(jax.jit, donate_argnums=(1, 2))
        def loop(spmv, pr, r, inv_deg, tol, max_push):
            return _push_while(pr, r, inv_deg, tol, max_push, spmv,
                               num_nodes=n, damping=damping,
                               dangling=dangling)

        run = jax.tree_util.Partial(loop, spmv_fn(plan))

    cache[key] = run
    return run


def seed_query_state(plan: GraphPlan, *, damping: float = 0.85,
                     dangling: str = "none"):
    """The plan's jitted query seeding: ``init(seed, inv_deg) ->
    (pr0, r0)`` with ``pr0 = seed`` and ``r0 = x1 − x0`` — the first
    power-iteration step from the seed, so handing ``(pr0, r0)`` to
    ``residual_push_loop`` makes the push walk the chunk stepper's
    exact iterates for the same personalized query (cached per plan
    like the loop itself)."""
    if dangling not in ("none", "redistribute"):
        raise ValueError(f"unknown dangling policy {dangling!r}")
    key = ("push_seed", damping, dangling)
    cache = fused_loop_cache(plan)
    cached = cache.get(key)
    if cached is not None:
        return cached

    n = plan.num_nodes

    @jax.jit
    def init(spmv, seed, inv_deg):
        x1 = (1.0 - damping) * seed + damping * spmv(seed * inv_deg)
        if dangling == "redistribute":
            dang = (inv_deg == 0).astype(seed.dtype)
            x1 = x1 + (seed * dang).sum() * (damping / n)
        return seed, x1 - seed

    init = jax.tree_util.Partial(init, spmv_fn(plan))
    cache[key] = init
    return init
