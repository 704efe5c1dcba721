"""GraphPlan serialization (ISSUE 4 satellite): a saved+loaded plan
equals a freshly built one — same schedule arrays, same SpMV output
(≤1e-6) — for every registered backend, sharded included; and
``install_plan`` warm-starts the process cache so loading replaces
building."""
import numpy as np
import pytest
import jax.numpy as jnp

import repro
from repro.core import SpMVEngine
from repro.core.plan import (PlanConfig, build_plan, graph_fingerprint,
                             install_plan, plan_cache_stats)
from repro.graphs import generators, io as graph_io


@pytest.fixture
def graph():
    return generators.rmat(7, 6, seed=17)


def _cfg(method):
    # num_shards=1 keeps the sharded backend tier-1 (single device)
    return PlanConfig(method=method, part_size=32, num_shards=1)


ALL_METHODS = ["pdpr", "bvgas", "pcpm", "pcpm_pallas", "pcpm_sharded"]


@pytest.mark.parametrize("method", ALL_METHODS)
class TestRoundTrip:
    def test_arrays_and_spmv_match_fresh_build(self, graph, method,
                                               tmp_path):
        fresh = build_plan(graph, _cfg(method))
        path = str(tmp_path / "plan.npz")
        fresh.save(path)
        loaded = repro.GraphPlan.load(path)

        assert loaded.config == fresh.config
        assert loaded.num_nodes == fresh.num_nodes
        assert loaded.num_edges == fresh.num_edges
        for key in ("csc_src", "csc_dst", "bv_src", "bv_dst"):
            a, b = getattr(fresh, key), getattr(loaded, key)
            assert (a is None) == (b is None)
            if a is not None:
                np.testing.assert_array_equal(a, b)
        if fresh.png is not None:
            for key in ("update_src", "update_offsets",
                        "edge_update_idx", "edge_dst", "edge_offsets"):
                np.testing.assert_array_equal(
                    getattr(fresh.png, key), getattr(loaded.png, key))
        if fresh.schedule is not None:
            assert loaded.schedule.block == fresh.schedule.block
            assert (loaded.schedule.window_rows
                    == fresh.schedule.window_rows)
            assert ((loaded.schedule.window_start is None)
                    == (fresh.schedule.window_start is None))
            if method == "pcpm":      # the expand kernel's windows
                assert fresh.schedule.window_start is not None
            for key in ("edge_update_idx_padded", "piece_start",
                        "piece_end", "piece_dst", "window_start"):
                a = getattr(fresh.schedule, key)
                if a is not None:
                    np.testing.assert_array_equal(
                        a, getattr(loaded.schedule, key))
        if fresh.blocked is not None:
            for key in ("update_src", "edge_update_local",
                        "edge_dst_local"):
                np.testing.assert_array_equal(
                    getattr(fresh.blocked, key),
                    getattr(loaded.blocked, key))
        if fresh.sharded is not None:
            assert loaded.sharded.num_shards == fresh.sharded.num_shards
            assert loaded.sharded.shard_size == fresh.sharded.shard_size
            for key in ("send_ids", "edge_upd", "edge_dst",
                        "eui_padded", "piece_start", "piece_end",
                        "piece_dst"):
                np.testing.assert_array_equal(
                    getattr(fresh.sharded, key),
                    getattr(loaded.sharded, key))

        x = np.random.default_rng(3).random(
            graph.num_nodes).astype(np.float32)
        y_fresh = np.asarray(SpMVEngine(graph, plan=fresh)(
            jnp.asarray(x)))
        y_loaded = np.asarray(SpMVEngine(graph, plan=loaded)(
            jnp.asarray(x)))
        assert np.abs(y_fresh - y_loaded).max() <= 1e-6

    def test_compression_ratio_survives(self, graph, method, tmp_path):
        fresh = build_plan(graph, _cfg(method))
        path = str(tmp_path / "plan.npz")
        fresh.save(path)
        loaded = repro.GraphPlan.load(path)
        assert loaded.compression_ratio == pytest.approx(
            fresh.compression_ratio)


class TestWarmStart:
    def test_install_plan_replaces_building(self, graph, tmp_path):
        path = str(tmp_path / "plan.npz")
        build_plan(graph, _cfg("pcpm")).save(path)
        # a "fresh process": same edges, new Graph object, empty cache
        g2 = generators.rmat(7, 6, seed=17)
        repro.clear_plan_cache()
        install_plan(g2, repro.GraphPlan.load(path))
        sess = repro.open(g2, method="pcpm", part_size=32, num_shards=1)
        assert plan_cache_stats().plan_builds == 0     # loaded, not built
        res = sess.pagerank(num_iterations=10)
        from repro.core import pagerank_reference
        np.testing.assert_allclose(
            np.asarray(res.ranks),
            pagerank_reference(graph, num_iterations=10),
            rtol=1e-3, atol=1e-7)

    def test_registry_load_with_plan_path(self, graph, tmp_path):
        from repro.serve import GraphRegistry
        gpath = str(tmp_path / "g.npz")
        ppath = str(tmp_path / "g.plan.npz")
        graph_io.save(gpath, graph)
        build_plan(graph, _cfg("pcpm")).save(ppath)
        repro.clear_plan_cache()
        reg = GraphRegistry(slots=2, chunk=4)
        sch = reg.load("g", gpath, plan_path=ppath)
        assert plan_cache_stats().plan_builds == 0     # warm-loaded
        assert sch.engine.method == "pcpm"
        assert sch.engine.partitioning.part_size == 32
        reg.submit("g", tol=0.0, max_iters=10)
        out = reg.run_until_drained()["g"]
        from repro.core import pagerank_reference
        np.testing.assert_allclose(
            out[0].ranks, pagerank_reference(graph, num_iterations=10),
            rtol=1e-3, atol=1e-7)

    def test_fingerprint_content_addressed(self, graph):
        g_same = generators.rmat(7, 6, seed=17)
        g_diff = generators.rmat(7, 6, seed=18)
        assert graph_fingerprint(graph) == graph_fingerprint(g_same)
        assert graph_fingerprint(graph) != graph_fingerprint(g_diff)

    def test_install_plan_rejects_wrong_graph(self, graph, tmp_path):
        """A plan from a different graph must never seed the cache —
        silently serving wrong preprocessing is the failure mode."""
        path = str(tmp_path / "plan.npz")
        build_plan(graph, _cfg("pcpm")).save(path)
        plan = repro.GraphPlan.load(path)
        g_other = generators.rmat(7, 6, seed=18)   # same n, other edges
        assert g_other.num_nodes == graph.num_nodes
        with pytest.raises(ValueError, match="mismatch"):
            install_plan(g_other, plan)
        g_small = generators.rmat(6, 6, seed=18)   # different n
        with pytest.raises(ValueError, match="mismatch"):
            install_plan(g_small, plan)

    def test_engine_rejects_foreign_plan(self, graph, tmp_path):
        """SpMVEngine(g, plan=...) applies the same plan/graph guard
        as install_plan."""
        path = str(tmp_path / "plan.npz")
        build_plan(graph, _cfg("pcpm")).save(path)
        plan = repro.GraphPlan.load(path)
        g_other = generators.rmat(7, 6, seed=18)
        with pytest.raises(ValueError, match="mismatch"):
            SpMVEngine(g_other, plan=plan)
        g_small = generators.rmat(6, 6, seed=18)
        with pytest.raises(ValueError, match="mismatch"):
            SpMVEngine(g_small, plan=plan)

    def test_oversized_sharded_plan_rejected(self, graph, tmp_path):
        """A sharded plan wanting more shards than this runtime has
        devices must raise (the mesh would otherwise silently truncate
        against the plan's fixed-shape shard arrays)."""
        import jax
        from repro.core.distributed import build_sharded_png
        too_many = jax.device_count() + 1
        plan = repro.GraphPlan(
            PlanConfig(method="pcpm_sharded", num_shards=too_many),
            graph.num_nodes, graph.num_edges,
            build_plan(graph, _cfg("pcpm_sharded")).partitioning,
            sharded=build_sharded_png(graph, too_many))
        path = str(tmp_path / "big.plan.npz")
        plan.save(path)
        loaded = repro.GraphPlan.load(path)
        with pytest.raises(ValueError, match="devices"):
            SpMVEngine(graph, plan=loaded)
        with pytest.raises(ValueError, match="num_shards"):
            install_plan(graph, loaded)

    def test_shard_axis_name_shares_plan(self, graph):
        """The mesh axis name is a run-layer knob — plans for the same
        graph must not duplicate per axis name."""
        p1 = build_plan(graph, PlanConfig(method="pcpm_sharded",
                                          num_shards=1))
        builds = plan_cache_stats().plan_builds
        p2 = build_plan(graph, PlanConfig(method="pcpm_sharded",
                                          num_shards=1, shard_axis="x"))
        assert p2 is p1
        assert plan_cache_stats().plan_builds == builds

    def test_irrelevant_gather_block_shares_plan(self, graph):
        """Backends that never consume gather_block normalize it out
        of the cache key — no duplicate builds for irrelevant knobs."""
        for method in ("pcpm_pallas",):
            e1 = SpMVEngine(graph, method=method, part_size=32)
            builds = plan_cache_stats().plan_builds
            e2 = SpMVEngine(graph, plan=build_plan(
                graph, PlanConfig(method=method, part_size=32,
                                  gather_block=512)))
            assert plan_cache_stats().plan_builds == builds, method
            assert e1.plan is e2.plan
        # ...but the blocked-gather engines genuinely depend on it:
        # distinct plans per block (pdpr/bvgas joined pcpm when they
        # adopted the hierarchical gather schedule)
        for method in ("pdpr", "bvgas", "pcpm"):
            p1 = build_plan(graph, PlanConfig(method=method,
                                              part_size=32))
            p2 = build_plan(graph, PlanConfig(method=method,
                                              part_size=32,
                                              gather_block=512))
            assert p1 is not p2 and p2.schedule.block == 512

    def test_evict_plans_releases_cache_entries(self, graph):
        from repro.core.plan import evict_plans
        sess = repro.open(graph, method="pcpm", part_size=32)
        assert evict_plans(graph) >= 1
        # live sessions keep serving from their plan reference
        res = sess.pagerank(num_iterations=5)
        assert res.iterations == 5
        # the next build is a rebuild, not a hit
        builds = plan_cache_stats().plan_builds
        repro.open(graph, method="pcpm", part_size=32)
        assert plan_cache_stats().plan_builds == builds + 1
