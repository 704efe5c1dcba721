"""PageRank driver (paper eq. 1/2) over any SpMV engine.

Matches the paper's algorithms: ranks are stored SCALED (PR/|N_o|)
during iteration (alg. 1 line 3 / alg. 2) and unscaled at the end.
Dangling nodes (|N_o| = 0) contribute nothing downstream, matching the
paper's implicit behaviour; their own rank is still computed.

Two drivers (DESIGN.md §4):

- ``driver="fused"`` (default): the whole power iteration is ONE
  donated, jitted ``lax.while_loop`` — rank buffers never leave the
  device, the L1 residual is computed on device, and the ``tol`` early
  exit is decided on device every ``check_every`` iterations.  Zero
  host transfers inside the loop; one dispatch for the entire run.
- ``driver="python"``: the original per-iteration Python loop, kept as
  a debug fallback (and used automatically for ``two_phase`` engines,
  whose host-side phase barrier cannot exist under jit).  It blocks on
  a host float once per iteration.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs.formats import Graph
from ..obs.trace import phase
from .spmv import SpMVEngine

# named scope of the loops' own work around the SpMV pass (inverse-
# degree scaling, damping, dangling mass, residual, exit test); the
# pass itself is under core/spmv.py's ``pcpm.*`` scopes
APPLY_SCOPE = "pagerank.apply"


@dataclasses.dataclass
class PageRankResult:
    ranks: jnp.ndarray       # unscaled PR vector
    iterations: int
    residuals: list


def _inv_degree(g: Graph) -> jnp.ndarray:
    out_deg = np.asarray(g.out_degree)
    return jnp.asarray(
        np.where(out_deg == 0, 0.0, 1.0 / np.maximum(out_deg, 1))
    ).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Fused driver
# ---------------------------------------------------------------------------
def fused_power_iteration(engine: SpMVEngine, *, damping: float = 0.85,
                          num_iterations: int = 20, tol: float = 0.0,
                          check_every: int = 1, multi: bool = False,
                          dangling: str = "none"):
    """Build (and cache on the engine) the jitted fused iteration loop.

    Returns a callable ``run(pr0, inv_deg, base) -> (pr, it, residuals)``
    (a ``Partial`` that passes the plan's streams to the jitted loop as
    arguments; see ``compile_bound``) where ``pr0`` is donated,
    ``base`` is the already-(1-damping)-scaled teleport vector (same
    shape as ``pr0``; a uniform vector for plain PageRank, per-column
    seed distributions for personalized queries), and ``residuals`` is a (num_iterations,) device array with -1.0 in
    slots where convergence was not checked.

    With ``multi=True`` the state is (n, d) — d independent rank vectors
    iterated in lockstep (the batched/personalized serving shape); the
    recorded residual is the max over columns and the loop exits only
    once every column is below ``tol``.

    The L1 residual is evaluated every ``check_every`` iterations (and
    on the last), so ``tol`` no longer costs a per-step reduction, let
    alone the Python driver's per-step host sync.

    ``dangling="redistribute"`` adds sink handling: the rank mass
    parked on zero-out-degree nodes is summed each step and
    redistributed over the teleport distribution (``base`` rescaled by
    ``damping / (1 - damping)``), so total mass is conserved at 1.  The
    default ``"none"`` keeps the paper's implicit drop-the-mass
    behaviour.
    """
    if dangling not in ("none", "redistribute"):
        raise ValueError(f"unknown dangling policy {dangling!r}")
    key = ("fused", damping, num_iterations, tol, check_every, multi,
           dangling)
    cached = engine._fused_cache.get(key)
    if cached is not None:
        return cached

    @partial(jax.jit, donate_argnums=(1,))
    def run(spmv, pr, inv_deg, base):
        with jax.named_scope(APPLY_SCOPE):
            if multi:
                inv_deg = inv_deg[:, None]
            # loop-invariant sink terms — XLA hoists both out of the body
            dang = (inv_deg == 0).astype(pr.dtype)
            redist = base * (damping / (1.0 - damping))
            residuals0 = jnp.full((max(num_iterations, 1),), -1.0,
                                  dtype=jnp.float32)

        def cond(state):
            it, _, _, done = state
            with jax.named_scope(APPLY_SCOPE):
                return (it < num_iterations) & ~done

        def body(state):
            it, pr, residuals, done = state
            with jax.named_scope(APPLY_SCOPE):
                spr = pr * inv_deg              # scaled ranks (alg.1 l.3)
            y = spmv(spr)
            with jax.named_scope(APPLY_SCOPE):
                pr_next = base + damping * y
                if dangling == "redistribute":
                    dmass = (pr * dang).sum(axis=0)
                    pr_next = pr_next + dmass * redist
                check = (((it + 1) % check_every == 0)
                         | (it + 1 >= num_iterations))
                res = jnp.where(
                    check, jnp.abs(pr_next - pr).sum(axis=0).max()
                    if multi else jnp.abs(pr_next - pr).sum(), -1.0)
                residuals = residuals.at[it].set(res)
                if tol > 0:
                    done = done | (check & (res >= 0) & (res < tol))
                return it + 1, pr_next, residuals, done

        it, pr, residuals, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), pr, residuals0, jnp.bool_(False)))
        return pr, it, residuals

    run = jax.tree_util.Partial(run, engine.spmv_fn())
    engine._fused_cache[key] = run
    return run


def masked_chunk_stepper(engine: SpMVEngine, *, damping: float = 0.85,
                         chunk: int = 8, dangling: str = "none"):
    """Chunked variant of the fused loop for continuous-batching query
    serving (DESIGN.md §7): the state is a (n, B) slot pool of
    independent rank vectors, each column carrying its OWN convergence
    state, and one call advances every still-active column by up to
    ``chunk`` iterations as a single donated device dispatch.

    Returns ``step(pr, base, active, tol_col, budget, inv_deg) ->
    (pr, active, took, res)``:

    - ``pr/base`` (n, B): rank state and per-column (1-damping)-scaled
      teleport vectors; ``pr`` is donated.
    - ``active`` (B,) bool: columns still iterating.  Converged (or
      empty) columns are FROZEN — masked out of the damping update so
      their ranks stay bit-identical while neighbours keep iterating.
    - ``tol_col`` (B,) f32 / ``budget`` (B,) i32: per-column tolerance
      and remaining-iteration allowance.  Both are DATA, not trace
      constants, so per-request tol/max_iters never retrace.
    - outputs: updated ``pr``; ``active`` with newly converged or
      budget-exhausted columns cleared; ``took`` (B,) i32 iterations
      actually executed per column this chunk; ``res`` (B,) f32 last
      L1 residual per column (-1 for columns that never ran).

    The chunk loop is a ``lax.while_loop`` that exits as soon as every
    column froze, so a nearly-drained pool doesn't pay ``chunk`` full
    SpMV passes.  The SpMV itself always runs on the full (n, B) state
    (static shapes — the TPU constraint); frozen columns simply have
    their update discarded, which is exactly what makes one multi-
    vector pass the cheap unit of work the scheduler batches over.
    """
    if dangling not in ("none", "redistribute"):
        raise ValueError(f"unknown dangling policy {dangling!r}")
    key = ("chunk", damping, chunk, dangling)
    cached = engine._fused_cache.get(key)
    if cached is not None:
        return cached

    @partial(jax.jit, donate_argnums=(1,))
    def step(spmv, pr, base, active, tol_col, budget, inv_deg):
        with jax.named_scope(APPLY_SCOPE):
            inv_col = inv_deg[:, None]
            dang_col = (inv_col == 0).astype(pr.dtype)
            redist = base * (damping / (1.0 - damping))
            took0 = jnp.zeros(pr.shape[1], dtype=jnp.int32)
            res0 = jnp.full((pr.shape[1],), -1.0, dtype=jnp.float32)

        def cond(state):
            i, _, act, _, _ = state
            with jax.named_scope(APPLY_SCOPE):
                return (i < chunk) & act.any()

        def body(state):
            i, pr, act, took, res = state
            with jax.named_scope(APPLY_SCOPE):
                spr = pr * inv_col              # scaled ranks (alg.1 l.3)
            y = spmv(spr)
            with jax.named_scope(APPLY_SCOPE):
                pr_next = base + damping * y
                if dangling == "redistribute":
                    dmass = (pr * dang_col).sum(axis=0)       # (B,)
                    pr_next = pr_next + dmass[None, :] * redist
                r = jnp.abs(pr_next - pr).sum(axis=0)         # (B,) per slot
                pr = jnp.where(act[None, :], pr_next, pr)     # freeze others
                res = jnp.where(act, r, res)
                took = took + act.astype(jnp.int32)
                # quarantine guardrail (DESIGN.md §10): a non-finite L1
                # residual means the column is NaN/Inf-poisoned — freeze
                # it immediately (NaN already compares False below, but
                # +Inf would keep burning budget) so the host sees the
                # non-finite residual and quarantines the slot.  Folded
                # into the existing reduction: no extra device sync.
                act = (act & jnp.isfinite(r) & (r >= tol_col)
                       & (took < budget))
                return i + 1, pr, act, took, res

        _, pr, active, took, res = jax.lax.while_loop(
            cond, body, (jnp.int32(0), pr, active, took0, res0))
        return pr, active, took, res

    step = jax.tree_util.Partial(step, engine.spmv_fn())
    engine._fused_cache[key] = step
    return step


def compile_bound(loop, *specs, donate_argnums=(0,), on_trace=None):
    """AOT-compile a loop built above (a ``Partial`` of a jitted
    function over the plan's streams) for ``specs``, with the streams
    as arguments and ``on_trace()`` run at each trace.  Returns the
    executable with the streams bound: ``(*args) -> outputs``."""
    jitted, bound = loop.func, loop.args

    def traced(*args):
        if on_trace is not None:
            on_trace()
        return jitted.__wrapped__(*args)

    donate = tuple(len(bound) + i for i in donate_argnums)
    compiled = (jax.jit(traced, donate_argnums=donate)
                .lower(*bound, *specs).compile())
    return partial(compiled, *bound)


def _run_fused(g: Graph, eng: SpMVEngine, *, num_iterations: int,
               damping: float, tol: float, check_every: int,
               dangling: str) -> PageRankResult:
    if eng.backend.supports_sharding:
        # a sharding backend owns its own fused loop (all-to-all +
        # blocked gather + psum residual under shard_map)
        from .distributed import distributed_pagerank
        return distributed_pagerank(
            g, eng.mesh, eng.shard_axis, num_iterations=num_iterations,
            damping=damping, tol=tol, check_every=check_every,
            dangling=dangling, layout=eng.sharded_layout,
            fused_cache=eng._fused_cache)
    n = g.num_nodes
    run = fused_power_iteration(eng, damping=damping,
                                num_iterations=num_iterations, tol=tol,
                                check_every=check_every,
                                dangling=dangling)
    with phase("repro.solve.inputs"):
        pr0 = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
        base = jnp.full((n,), (1.0 - damping) / n, dtype=jnp.float32)
        # waited for here, so that the device's wait for the upload
        # lies inside this span and not in the next one
        inputs = jax.block_until_ready((pr0, _inv_degree(g), base))
    with phase("repro.solve.run"):
        pr, it, res = run(*inputs)
    with phase("repro.solve.readback"):
        res_host = np.asarray(res)[:int(it)]
    return PageRankResult(pr, int(it),
                          [float(r) for r in res_host if r >= 0.0])


# ---------------------------------------------------------------------------
# Python-loop driver (debug fallback; syncs on the host every iteration)
# ---------------------------------------------------------------------------
def _run_python(g: Graph, eng: SpMVEngine, *, num_iterations: int,
                damping: float, tol: float,
                dangling: str = "none") -> PageRankResult:
    n = g.num_nodes
    inv_deg = _inv_degree(g)
    dang = (inv_deg == 0).astype(jnp.float32)
    pr = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    base = (1.0 - damping) / n
    residuals = []
    it = 0
    for it in range(1, num_iterations + 1):
        spr = pr * inv_deg
        pr_next = base + damping * eng(spr)   # A^T @ SPR
        if dangling == "redistribute":
            pr_next = pr_next + (pr * dang).sum() * (damping / n)
        res = float(jnp.abs(pr_next - pr).sum())
        residuals.append(res)
        pr = pr_next
        if tol and res < tol:
            break
    return PageRankResult(pr, it, residuals)


def pagerank(g: Graph, *, method: str = "pcpm", num_iterations: int = 20,
             damping: float = 0.85, part_size: int | None = None,
             tol: float = 0.0, engine: SpMVEngine | None = None,
             driver: str = "fused", check_every: int = 1,
             dangling: str = "none") -> PageRankResult:
    """Compatibility front-end.  ``method`` is resolved through the
    backend registry and the graph plan comes from the process-level
    plan cache, so repeated calls on one graph never re-sort edges.
    New code should prefer ``repro.open(g, cfg).pagerank()``."""
    eng = engine or SpMVEngine(g, method=method, part_size=part_size)
    if driver == "python" or eng.two_phase:
        # the engine's __call__ already maps reordered plans back to
        # the original labeling per pass — nothing to do here
        return _run_python(g, eng, num_iterations=num_iterations,
                           damping=damping, tol=tol, dangling=dangling)
    if driver != "fused":
        raise ValueError(f"unknown driver {driver!r}")
    if eng.plan.reorder_perm is None:
        return _run_fused(g, eng, num_iterations=num_iterations,
                          damping=damping, tol=tol,
                          check_every=check_every, dangling=dangling)
    # reordered plan: iterate wholly in internal (relabeled) space —
    # the uniform start/teleport vectors are permutation-invariant, so
    # only the FINAL ranks pay one gather back to the original ids
    from .backends import reorder_device
    from .plan import internal_graph
    res = _run_fused(internal_graph(g, eng.plan), eng,
                     num_iterations=num_iterations, damping=damping,
                     tol=tol, check_every=check_every, dangling=dangling)
    perm, _ = reorder_device(eng.plan)
    res.ranks = jnp.take(res.ranks, perm, axis=0)
    return res


def pagerank_reference(g: Graph, *, num_iterations: int = 20,
                       damping: float = 0.85,
                       dangling: str = "none") -> np.ndarray:
    """Dense numpy oracle for tests (small graphs only)."""
    n = g.num_nodes
    A = np.zeros((n, n), dtype=np.float64)
    np.add.at(A, (g.src, g.dst), 1.0)
    deg = np.maximum(g.out_degree, 1).astype(np.float64)
    inv = np.where(g.out_degree == 0, 0.0, 1.0 / deg)
    sink = (np.asarray(g.out_degree) == 0).astype(np.float64)
    pr = np.full(n, 1.0 / n)
    for _ in range(num_iterations):
        y = A.T @ (pr * inv)
        if dangling == "redistribute":
            y = y + (pr * sink).sum() / n
        pr = (1 - damping) / n + damping * y
    return pr
