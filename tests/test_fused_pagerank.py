"""Fused-driver + pcpm_pallas engine coverage (ISSUE 1):

- parity of the fused `lax.while_loop` driver and the Pallas engine
  against the dense oracle across part sizes (single-partition and
  empty-partition shapes included);
- d > 1 multi-vector SpMV and batched personalized serving;
- dangling nodes;
- tol-based early exit identical to the Python-loop debug driver;
- zero device->host transfers inside the fused iteration loop
  (enforced with jax's transfer guard);
- AOT-compiled serving path never retraces per request.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.graphs import Graph, from_edge_list, generators
from repro.core import (SpMVEngine, fused_power_iteration, pagerank,
                        pagerank_reference)
from repro.core.pagerank import _inv_degree
from repro.serve import PageRankServer


def dense_spmv(g: Graph, x: np.ndarray) -> np.ndarray:
    A = np.zeros((g.num_nodes, g.num_nodes))
    np.add.at(A, (g.src, g.dst), 1.0)
    return A.T @ x


# --------------------------------------------------------------- parity
class TestParity:
    # part sizes straddle the node count: 512 > n for scale 7 (=128
    # nodes per rmat pow) ... part_size >= n gives partition count 1.
    @pytest.mark.parametrize("method", ["pcpm", "pcpm_pallas"])
    @pytest.mark.parametrize("part_size", [16, 64, 1 << 20])
    def test_pagerank_vs_dense_oracle(self, method, part_size):
        g = generators.rmat(7, 8, seed=9)
        res = pagerank(g, method=method, num_iterations=20,
                       part_size=part_size)
        ref = pagerank_reference(g, num_iterations=20)
        np.testing.assert_allclose(np.asarray(res.ranks), ref, rtol=1e-3)

    def test_single_partition(self):
        g = generators.rmat(6, 4, seed=3)
        eng = SpMVEngine(g, method="pcpm_pallas",
                         part_size=g.num_nodes)
        assert eng.partitioning.num_partitions == 1
        x = np.random.default_rng(0).random(g.num_nodes).astype(np.float32)
        np.testing.assert_allclose(np.asarray(eng(jnp.asarray(x))),
                                   dense_spmv(g, x), rtol=2e-4, atol=1e-5)

    def test_empty_partitions(self):
        # all edges land in partition 0; partitions 1..7 are empty
        n = 64
        e = np.stack([np.arange(1, n), np.zeros(n - 1, dtype=np.int64)], 1)
        g = from_edge_list(n, e)
        for method in ("pcpm", "pcpm_pallas"):
            eng = SpMVEngine(g, method=method, part_size=8)
            x = np.random.default_rng(1).random(n).astype(np.float32)
            np.testing.assert_allclose(
                np.asarray(eng(jnp.asarray(x))), dense_spmv(g, x),
                rtol=2e-4, atol=1e-5)

    def test_multivector_pallas(self):
        g = generators.uniform_random(300, 3000, seed=7)
        eng = SpMVEngine(g, method="pcpm_pallas", part_size=64)
        x = np.random.default_rng(2).random((300, 16)).astype(np.float32)
        y = np.asarray(eng(jnp.asarray(x)))
        np.testing.assert_allclose(y, dense_spmv(g, x), rtol=2e-4,
                                   atol=1e-5)

    def test_dangling_nodes_fused(self):
        g = from_edge_list(4, np.array([[0, 1], [1, 2], [2, 3], [0, 3]]))
        for method in ("pcpm", "pcpm_pallas"):
            res = pagerank(g, method=method, num_iterations=30,
                           part_size=2)
            ref = pagerank_reference(g, num_iterations=30)
            np.testing.assert_allclose(np.asarray(res.ranks), ref,
                                       rtol=1e-4)


# ------------------------------------------------- dangling redistribution
class TestDanglingRedistribution:
    def test_mass_conserved_and_matches_oracle(self):
        g = generators.rmat(8, 4, seed=21)     # rmat leaves sinks
        assert (np.asarray(g.out_degree) == 0).any()
        res = pagerank(g, method="pcpm", num_iterations=25,
                       dangling="redistribute")
        ref = pagerank_reference(g, num_iterations=25,
                                 dangling="redistribute")
        np.testing.assert_allclose(np.asarray(res.ranks), ref,
                                   rtol=1e-3, atol=1e-7)
        assert abs(float(np.asarray(res.ranks).sum()) - 1.0) < 1e-5

    def test_python_driver_agrees(self):
        g = generators.rmat(7, 4, seed=22)
        eng = SpMVEngine(g, method="pcpm", part_size=32)
        fused = pagerank(g, engine=eng, num_iterations=20,
                         dangling="redistribute")
        py = pagerank(g, engine=eng, num_iterations=20,
                      dangling="redistribute", driver="python")
        np.testing.assert_allclose(np.asarray(fused.ranks),
                                   np.asarray(py.ranks), rtol=1e-5,
                                   atol=1e-8)

    def test_unknown_policy_rejected(self):
        g = generators.rmat(6, 4, seed=23)
        with pytest.raises(ValueError, match="dangling"):
            pagerank(g, method="pcpm", dangling="drop-it")


# --------------------------------------- sharded engine on one device
class TestShardedSingleDevice:
    """The pcpm_sharded engine degenerates to 1 shard on the default
    single-device runtime — tier-1 coverage of the shard_map path
    without forcing host devices (the 8-device suites live in
    test_distributed.py / test_sharded_parity.py)."""

    def test_pagerank_end_to_end(self):
        g = generators.rmat(7, 8, seed=9)
        eng = SpMVEngine(g, method="pcpm_sharded")
        res = pagerank(g, engine=eng, num_iterations=20)
        ref = pagerank_reference(g, num_iterations=20)
        np.testing.assert_allclose(np.asarray(res.ranks), ref,
                                   rtol=1e-3, atol=1e-7)

    def test_pad_slots_leak_no_mass(self):
        # n chosen so the padded tail is non-empty at shard_size
        # granularity only when num_shards > 1; with 1 shard the
        # layout is pad-free, so force a ragged n via isolated tail
        g = generators.rmat(7, 6, seed=19)
        eng = SpMVEngine(g, method="pcpm_sharded")
        res = pagerank(g, engine=eng, num_iterations=30,
                       dangling="redistribute")
        mass = float(np.asarray(res.ranks).sum())
        assert abs(mass - 1.0) < 1e-5
        ref = pagerank_reference(g, num_iterations=30,
                                 dangling="redistribute")
        np.testing.assert_allclose(np.asarray(res.ranks), ref,
                                   rtol=1e-3, atol=1e-7)

    def test_spmv_matches_dense(self):
        g = generators.uniform_random(300, 3000, seed=7)
        eng = SpMVEngine(g, method="pcpm_sharded")
        x = np.random.default_rng(2).random((300, 4)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(eng(jnp.asarray(x))),
                                   dense_spmv(g, x), rtol=2e-4,
                                   atol=1e-5)

    def test_too_many_shards_rejected(self):
        g = generators.rmat(6, 4, seed=3)
        with pytest.raises(ValueError, match="num_shards"):
            SpMVEngine(g, method="pcpm_sharded",
                       num_shards=jax.device_count() + 1)


# ------------------------------------------------------------ early exit
class TestEarlyExit:
    def test_tol_exit_matches_python_driver(self):
        g = generators.rmat(8, 8, seed=10)
        eng = SpMVEngine(g, method="pcpm", part_size=64)
        fused = pagerank(g, engine=eng, num_iterations=60, tol=1e-5)
        py = pagerank(g, engine=eng, num_iterations=60, tol=1e-5,
                      driver="python")
        assert fused.iterations == py.iterations < 60
        # XLA fuses the loop body differently from the op-by-op driver;
        # identical math, f32 rounding differs in the last couple ulps.
        np.testing.assert_allclose(np.asarray(fused.ranks),
                                   np.asarray(py.ranks), rtol=1e-5,
                                   atol=1e-8)
        np.testing.assert_allclose(fused.residuals, py.residuals,
                                   rtol=5e-3, atol=1e-7)

    def test_check_every_defers_exit(self):
        g = generators.rmat(8, 8, seed=10)
        eng = SpMVEngine(g, method="pcpm", part_size=64)
        every = pagerank(g, engine=eng, num_iterations=60, tol=1e-5)
        coarse = pagerank(g, engine=eng, num_iterations=60, tol=1e-5,
                          check_every=7)
        # exit only on a check boundary, never before convergence
        assert coarse.iterations % 7 == 0 or coarse.iterations == 60
        assert coarse.iterations >= every.iterations
        assert coarse.residuals[-1] < 1e-5


# ----------------------------------------------------- device residency
class TestDeviceResidency:
    def test_no_host_transfers_inside_loop(self):
        """The fused loop must run to completion without a single
        device->host transfer — the Python driver's per-iteration
        float() sync would trip the guard."""
        g = generators.rmat(8, 8, seed=11)
        eng = SpMVEngine(g, method="pcpm", part_size=64)
        run = fused_power_iteration(eng, num_iterations=15, tol=1e-12)
        n = g.num_nodes
        pr0 = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
        base = jnp.full((n,), 0.15 / n, dtype=jnp.float32)
        inv_deg = _inv_degree(g)
        with jax.transfer_guard_device_to_host("disallow"):
            pr, it, res = run(pr0, inv_deg, base)
            pr.block_until_ready()
        assert int(it) == 15

    def test_loop_is_one_device_program(self):
        """Structural: the fused driver lowers to a single `while`
        primitive with no host callbacks — the whole iteration loop is
        one device dispatch (per check_every block there is only an
        on-device branch, never a host round-trip)."""
        g = generators.rmat(6, 4, seed=12)
        eng = SpMVEngine(g, method="pcpm", part_size=16)
        run = fused_power_iteration(eng, num_iterations=5, tol=1e-6,
                                    check_every=2)
        n = g.num_nodes
        pr0 = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
        base = jnp.full((n,), 0.15 / n, dtype=jnp.float32)
        jaxpr = jax.make_jaxpr(run.func.__wrapped__)(
            *run.args, pr0, _inv_degree(g), base)
        prims = [str(e.primitive) for e in jaxpr.jaxpr.eqns]
        assert prims.count("while") == 1
        assert not any("callback" in p or "infeed" in p or "outfeed" in p
                       for p in prims)


# --------------------------------------------------------------- serving
class TestServing:
    def test_aot_no_retrace_per_request(self):
        g = generators.rmat(7, 6, seed=13)
        srv = PageRankServer(g, method="pcpm_pallas", part_size=32,
                             num_iterations=10)
        assert srv.trace_count == 1          # traced once, at lowering
        for _ in range(3):
            pr, it, _ = srv.query()
            assert it == 10
        assert srv.trace_count == 1          # zero traces per request

    def test_batched_personalized_queries(self):
        g = generators.rmat(7, 8, seed=14)
        n, d = g.num_nodes, 3
        srv = PageRankServer(g, method="pcpm", part_size=32, batch=d,
                             num_iterations=30)
        seeds = np.zeros((n, d), np.float32)
        seeds[5, 0] = seeds[17, 1] = seeds[33, 2] = 1.0
        pr, it, _ = srv.query(seeds)
        assert pr.shape == (n, d)
        # dense personalized oracle, per column
        A = np.zeros((n, n))
        np.add.at(A, (g.src, g.dst), 1.0)
        inv = np.where(g.out_degree == 0, 0.0,
                       1.0 / np.maximum(g.out_degree, 1))
        for j in range(d):
            v = seeds[:, j] / seeds[:, j].sum()
            x = v.copy()
            for _ in range(it):
                x = 0.15 * v + 0.85 * (A.T @ (x * inv))
            np.testing.assert_allclose(np.asarray(pr)[:, j], x,
                                       rtol=1e-3, atol=1e-7)
