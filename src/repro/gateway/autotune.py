"""Slot-pool size autotune: pick B from measured stepper cost.

The scheduler's hardcoded ``slots=4`` is a guess.  The real tradeoff:
a chunk dispatch costs roughly ``chunk * t_pass(B)`` where
``t_pass(B)`` is one multi-vector SpMV pass over an (n, B) state —
sublinear in B on wide hardware (the PCPM batching property), so
bigger pools amortize better per query.  But every query admitted
into the pool waits a full chunk between drain opportunities, so
chunk latency IS the serving latency floor.  The tuner measures
``t_pass`` at each candidate B and picks the LARGEST pool whose
predicted chunk time stays under ``target_chunk_s`` — maximum
amortization that still honors the latency target.

The probe runs the engine's multi-vector SpMV directly (the dominant
term of a chunk step; the damping/residual epilogue is O(nB) and
shared), so probing never compiles a throwaway stepper — the real
stepper is compiled ONCE at the chosen B, keeping the scheduler's
``trace_count == 1`` invariant intact.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class AutotuneReport:
    """What the tuner measured and chose — attached to gateway stats
    and to ``Session.gateway()`` so the decision is auditable."""
    target_chunk_s: float
    chunk: int
    probes: dict[int, float]          # B -> min measured chunk seconds
    chosen: int

    def summary(self) -> dict:
        return {"target_chunk_s": self.target_chunk_s,
                "chunk": self.chunk, "chosen": self.chosen,
                "probes_ms": {str(b): t * 1e3
                              for b, t in self.probes.items()}}


def autotune_slots(engine, *, chunk: int,
                   target_chunk_s: float = 0.025,
                   candidates: tuple = (2, 4, 8, 16, 32, 64),
                   repeats: int = 3, default: int = 4) -> AutotuneReport:
    """Measure ``chunk`` * t_pass(B) for ascending candidate pool
    sizes and return the largest B under ``target_chunk_s``.

    Min-of-``repeats`` timing after one warmup dispatch per candidate
    (compile + first-touch excluded); probing stops early once a
    candidate exceeds the target — t_pass is monotone in B, larger
    pools can only be worse.  Falls back to ``default`` untouched for
    backends without multi-vector support (nothing to amortize)."""
    if not engine.backend.multi_vector:
        return AutotuneReport(target_chunk_s, chunk, {}, default)
    n = engine.num_nodes
    fn = jax.tree_util.Partial(jax.jit(lambda spmv, x: spmv(x)),
                               engine.spmv_fn())
    rng = np.random.default_rng(0)
    probes: dict[int, float] = {}
    for b in sorted(set(int(b) for b in candidates)):
        if b < 1 or b > n:
            continue
        x = rng.random((n, b), dtype=np.float32)
        jax.block_until_ready(fn(x))              # warmup: compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            best = min(best, time.perf_counter() - t0)
        probes[b] = best * chunk
        if probes[b] > target_chunk_s:
            break                                 # monotone — stop
    passing = [b for b, t in probes.items() if t <= target_chunk_s]
    chosen = (max(passing) if passing
              else min(probes) if probes else default)
    return AutotuneReport(target_chunk_s, chunk, probes, chosen)
