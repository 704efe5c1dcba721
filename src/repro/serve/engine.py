"""Serving engines.

1. ``PageRankServer`` — batched (personalized) PageRank queries over a
   fixed graph: the fused `lax.while_loop` power iteration is AOT
   compiled (``.lower().compile()``) once at construction, so a request
   pays zero trace/compile cost — it is one executable dispatch over
   donated device buffers (DESIGN.md §4).

2. ``ServeEngine`` — batched LM serving with continuous-batching slot
   management: a fixed pool of B slots shares one stacked KV cache
   (static shapes — the TPU constraint).  Requests are admitted into
   free slots; their prompts are prefilled token-by-token into the
   slot's cache region (per-slot positions via the vectorized decode
   path), then all active slots decode in lockstep.  Finished slots
   (EOS or max_new_tokens) free immediately and can be re-admitted
   without disturbing neighbours — the vLLM-style schedule reduced to
   its TPU-static essentials.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..configs.base import LMConfig
from ..core.backends import resolve_engine, reorder_device
from ..core.pagerank import (_inv_degree, compile_bound,
                             fused_power_iteration)
from ..core.plan import internal_graph, reorder_inverse
from ..core.spmv import SpMVEngine
from ..graphs.formats import Graph
from ..models import transformer as tf


# ---------------------------------------------------------------------------
# PageRank serving
# ---------------------------------------------------------------------------
def _mesh_shardings(engine: SpMVEngine):
    """(vector, matrix, replicated) NamedShardings on a pcpm_sharded
    engine's mesh — shared by both PageRank serving front-ends
    (``PageRankServer`` and ``serve.scheduler.SlotScheduler``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh, axis = engine.mesh, engine.shard_axis
    return (NamedSharding(mesh, P(axis)),
            NamedSharding(mesh, P(axis, None)),
            NamedSharding(mesh, P()))


def _sharded_inv_degree(g: Graph, engine: SpMVEngine, vec_sharding):
    """Padded inverse out-degree, uploaded vertex-sharded."""
    from ..core.distributed import _padded_inv_degree
    return jax.device_put(
        jnp.asarray(_padded_inv_degree(g, engine.sharded_layout)),
        vec_sharding)


def _normalize_teleport(host: np.ndarray) -> np.ndarray:
    """Validate and column-normalize teleport distributions (a single
    (n,) vector or (n, batch) columns)."""
    if host.ndim == 1:
        # scalar fast path — this sits on the per-query submit path of
        # the push route (thousands of queries/sec), where the array
        # variant's extra reduction passes are measurable
        s = float(host.sum())
        if not (s > 0.0 and np.isfinite(s)):   # NaN fails s > 0.0
            raise ValueError(
                "every seed column must be finite with positive mass; "
                f"got column sums {s!r}")
        return host / np.float32(s)
    sums = host.sum(axis=0)
    if not (np.isfinite(sums).all() and np.all(sums > 0)):
        raise ValueError(
            "every seed column must be finite with positive mass; "
            f"got column sums {sums!r}")
    return host / sums


class PageRankServer:
    """Serve (personalized) PageRank queries from a pre-compiled fused
    iteration loop.

    ``batch`` > 1 serves a batch of personalization (seed) vectors in
    lockstep as one (n, batch) multi-vector iteration — the PCPM SpMV
    engines and the Pallas kernel are multi-vector native, so a batch
    costs one SpMV pass, not ``batch`` passes.

    Construction does all the expensive work once: PNG build, engine
    layout upload, trace + lowering + compilation (``jax.jit(...)
    .lower(...).compile()``).  ``query()`` only stages already-compiled
    device work; it never retraces (``trace_count`` stays fixed, see
    tests/test_fused_pagerank.py).

    ``sharded=True`` serves from the multi-device engine instead: the
    graph is vertex-sharded over ``num_shards`` devices (default all)
    and the sharded fused loop — all-to-all scatter + blocked local
    gather + psum residual under ``shard_map`` (DESIGN.md §6) — is AOT
    compiled against the mesh, with explicitly sharded input avals so
    requests dispatch straight onto device-local buffers.
    """

    def __init__(self, g: Graph, *, method: str = "pcpm_pallas",
                 part_size: int | None = None, batch: int = 1,
                 damping: float = 0.85, num_iterations: int = 20,
                 tol: float = 0.0, check_every: int = 1,
                 dangling: str = "none", sharded: bool = False,
                 num_shards: int | None = None,
                 engine: SpMVEngine | None = None):
        self.g = g
        self.n = g.num_nodes
        self.batch = batch
        self.damping = damping
        self.engine = resolve_engine(g, method=method, sharded=sharded,
                                     part_size=part_size,
                                     num_shards=num_shards,
                                     engine=engine)
        self.sharded = self.engine.backend.supports_sharding
        self.trace_count = 0
        self._uniform_cache = None
        multi = batch > 1
        # reordered plans (DESIGN.md §12): iterate in the plan's
        # internal (relabeled) space — seeds map in at query, ranks
        # map back out, inverse degrees come from the internal graph
        self._perm = self.engine.plan.reorder_perm
        self._inv = (None if self._perm is None
                     else reorder_inverse(self.engine.plan))
        gi = internal_graph(g, self.engine.plan)

        if self.sharded:
            from ..core.distributed import sharded_power_iteration
            layout = self.engine.sharded_layout
            self._n_pad = layout.padded_nodes
            run = sharded_power_iteration(
                layout, self.engine.mesh, self.engine.shard_axis,
                damping=damping, num_iterations=num_iterations, tol=tol,
                check_every=check_every, multi=multi, dangling=dangling)
            self._vec_sharding, mat_sharding, _ = _mesh_shardings(
                self.engine)
            self._state_sharding = (mat_sharding if multi
                                    else self._vec_sharding)
            self._inv_deg = _sharded_inv_degree(gi, self.engine,
                                                self._vec_sharding)
            shape = ((self._n_pad, batch) if multi else (self._n_pad,))
            spec = jax.ShapeDtypeStruct(shape, jnp.float32,
                                        sharding=self._state_sharding)
            inv_spec = jax.ShapeDtypeStruct((self._n_pad,), jnp.float32,
                                            sharding=self._vec_sharding)
        else:
            run = fused_power_iteration(
                self.engine, damping=damping,
                num_iterations=num_iterations, tol=tol,
                check_every=check_every, multi=multi, dangling=dangling)
            self._n_pad = self.n
            self._inv_deg = _inv_degree(gi)
            shape = (self.n, batch) if multi else (self.n,)
            spec = jax.ShapeDtypeStruct(shape, jnp.float32)
            inv_spec = jax.ShapeDtypeStruct((self.n,), jnp.float32)

        def counted():
            self.trace_count += 1           # increments only at trace time

        self._compiled = compile_bound(run, spec, inv_spec, spec,
                                       on_trace=counted)

    def _upload(self, host: np.ndarray):
        if self.sharded:
            return jax.device_put(jnp.asarray(host),
                                  self._state_sharding)
        return jnp.asarray(host)

    def _uniform_batch(self):
        """The uniform-teleport batch, built once: the padded host
        array (the iteration state is donated, so it re-uploads per
        query, but is never re-materialized with ``np.full``) and the
        REUSABLE base device buffer (base is not donated)."""
        if self._uniform_cache is None:
            shape = (self.n, self.batch) if self.batch > 1 else (self.n,)
            host = np.full(shape, 1.0 / self.n, dtype=np.float32)
            if self.sharded:
                pad = self._n_pad - self.n
                host = np.pad(host,
                              ((0, pad),) + ((0, 0),) * (host.ndim - 1))
            base = self._upload((1.0 - self.damping) * host)
            self._uniform_cache = (host, base)
        return self._uniform_cache

    def query(self, seeds: np.ndarray | None = None):
        """Rank one batch.  ``seeds``: (n, batch) per-query teleport
        distributions (columns need not be normalized — they are), or
        None for the uniform-teleport batch.  Returns (ranks, iters,
        residuals) with ranks of shape (n, batch) (or (n,) when
        ``batch == 1``) and residuals as in ``PageRankResult`` (one
        float per convergence check, in iteration order)."""
        shape = (self.n, self.batch) if self.batch > 1 else (self.n,)
        if seeds is None:
            host, base = self._uniform_batch()
            v = self._upload(host)
        else:
            host = _normalize_teleport(
                np.asarray(seeds, dtype=np.float32).reshape(shape))
            if self._perm is not None:
                host = host[self._inv]        # into internal space
            if self.sharded:
                pad = self._n_pad - self.n
                host = np.pad(host,
                              ((0, pad),) + ((0, 0),) * (host.ndim - 1))
            v = self._upload(host)
            base = (1.0 - self.damping) * v
        pr, it, res = self._compiled(v, self._inv_deg, base)
        if self.sharded:
            pr = pr[:self.n]
        if self._perm is not None:            # back to original ids
            perm_dev, _ = reorder_device(self.engine.plan)
            pr = jnp.take(pr, perm_dev, axis=0)
        it = int(it)
        res_host = np.asarray(res)[:it]
        return pr, it, [float(r) for r in res_host if r >= 0.0]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None


class ServeEngine:
    def __init__(self, cfg: LMConfig, params, *, batch_slots: int = 4,
                 max_len: int = 256, eos_id: int = -1,
                 sample: Optional[Callable] = None):
        self.cfg = cfg
        self.params = params
        self.b = batch_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.sample = sample or (lambda logits: jnp.argmax(logits, -1))
        self.cache = tf.init_cache(cfg, batch_slots, max_len)
        self.t = np.zeros(batch_slots, dtype=np.int32)   # next position
        self.slot_req: list[Optional[Request]] = [None] * batch_slots
        self.pending_prompt: list[list[int]] = [[] for _ in range(batch_slots)]
        self._step = jax.jit(
            lambda params, cache, tok, t: tf.decode_step(
                params, cfg, cache, tok, t))

    # ---------------------------------------------------------- admission
    def fits(self, req: Request) -> bool:
        """Whether the request can EVER be admitted: prompt plus token
        budget must stay inside the static per-slot cache region (the
        last KV write for a full generation lands at position
        ``len(prompt) + max_new_tokens - 2``; anything longer would be
        truncated or, for prompts past ``max_len``, corrupt the
        slot)."""
        return len(req.prompt) + req.max_new_tokens <= self.max_len

    def add_request(self, req: Request) -> bool:
        if not self.fits(req):
            return False
        for i in range(self.b):
            if self.slot_req[i] is None:
                self.slot_req[i] = req
                self.pending_prompt[i] = list(req.prompt)
                self.t[i] = 0
                return True
        return False

    @property
    def active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    # -------------------------------------------------------------- step
    def step(self):
        """Advance every active slot by one token (prompt feed or
        generation), one batched decode_step."""
        tokens = np.zeros((self.b, 1), dtype=np.int32)
        feeding = [False] * self.b
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            if self.pending_prompt[i]:
                tokens[i, 0] = self.pending_prompt[i].pop(0)
                feeding[i] = True
            elif req.generated:
                tokens[i, 0] = req.generated[-1]
            elif req.prompt:
                tokens[i, 0] = req.prompt[-1]
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.t))
        next_tok = np.asarray(self.sample(logits[:, 0, :]))
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            self.t[i] += 1
            if feeding[i] and self.pending_prompt[i]:
                continue                         # still prefilling
            if not feeding[i] or not self.pending_prompt[i]:
                tok = int(next_tok[i])
                req.generated.append(tok)
                if (tok == self.eos_id
                        or len(req.generated) >= req.max_new_tokens
                        or self.t[i] >= self.max_len - 1):
                    req.done = True
                    self.slot_req[i] = None      # slot freed

    def run_until_drained(self, requests: list[Request],
                          max_steps: int = 10_000) -> list[Request]:
        queue = []
        for req in requests:
            # never-fitting requests are rejected up front instead of
            # blocking the head of the line forever
            if self.fits(req):
                queue.append(req)
            else:
                req.error = (f"prompt ({len(req.prompt)}) + "
                             f"max_new_tokens ({req.max_new_tokens})"
                             f" exceed max_len={self.max_len}")
                req.done = True
        for _ in range(max_steps):
            # every queued request fits, so admission only waits on a
            # free slot — no per-step queue rescans once the pool fills
            while queue and self.active < self.b:
                self.add_request(queue.pop(0))
            if not queue and self.active == 0:
                break
            if self.active:
                self.step()
        return requests
