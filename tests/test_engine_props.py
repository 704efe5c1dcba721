"""Hypothesis property tests for engine equivalence (split out of
test_core_pcpm.py so that module collects without ``hypothesis``)."""
import numpy as np
import pytest
import jax.numpy as jnp

pytest.importorskip(
    "hypothesis", reason="property tests need the [test] extra")
from hypothesis import given, settings, strategies as st

from repro.graphs import generators
from repro.core import SpMVEngine


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 7),
       st.sampled_from([4, 16, 64]))
def test_property_engines_agree(seed, scale, part_size):
    """Property: all engines compute the same y for random graphs,
    including empty partitions, self-loops, multi-edges."""
    g = generators.rmat(scale, 4, seed=seed)
    x = jnp.asarray(np.random.default_rng(seed).random(
        g.num_nodes).astype(np.float32))
    ys = [np.asarray(SpMVEngine(g, method=m, part_size=part_size)(x))
          for m in ("pdpr", "bvgas", "pcpm")]
    np.testing.assert_allclose(ys[0], ys[1], rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(ys[0], ys[2], rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# The pcpm schedule's expand-kernel windows (core/png.py)
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 10),
       st.sampled_from([4, 16, 64, 1024]), st.sampled_from([64, 256]))
def test_property_schedule_windows(seed, scale, part_size, gather_block):
    """Every arc's update lies in its kernel block's window, windows
    are whole tiles inside the bins, a window is filled at most once
    per partition, the arcs keep the pull order and pad arcs carry the
    ``num_nodes`` destination and point into their window."""
    from repro.core import Partitioning, build_png, build_gather_schedule
    g = generators.rmat(scale, 4, seed=seed)
    layout = build_png(g, Partitioning(g.num_nodes, part_size))
    sched = build_gather_schedule(layout, block=gather_block)
    kb, m = sched.kernel_block, g.num_edges
    eui = sched.edge_update_idx_padded
    assert kb % gather_block == 0 and len(eui) % kb == 0
    assert len(eui) - m < kb
    assert len(sched.window_start) == len(eui) // kb
    rows = -(-max(-(-layout.num_updates // 128), 1) // 8) * 8   # whole tiles
    win = sched.window_start
    assert sched.window_rows % 8 == 0 and (win % 8 == 0).all()
    assert ((win >= 0) & (win + sched.window_rows <= rows)).all()
    lo = np.repeat(win.astype(np.int64) * 128, kb)
    hi = lo + sched.window_rows * 128
    assert ((eui >= lo) & (eui < hi)).all()
    np.testing.assert_array_equal(eui[m:], lo[m:])
    np.testing.assert_array_equal(eui[:m], layout.edge_update_idx)
    fills = 1 + np.count_nonzero(win[1:] != win[:-1])
    assert fills <= layout.num_partitions
    covered = np.zeros(len(eui), bool)
    for a, b, d in zip(sched.piece_start, sched.piece_end,
                       sched.piece_dst):
        if d < g.num_nodes:
            assert (layout.edge_dst[a:b + 1] == d).all()
            covered[a:b + 1] = True
        else:
            assert a >= m
    assert covered[:m].all() and not covered[m:].any()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(3, 8),
       st.sampled_from([8, 64, 256]))
def test_property_pcpm_pagerank_matches_pdpr(seed, scale, part_size):
    """PageRank through the split expand and reduce over the windowed
    schedule equals the pdpr engine's bit for bit: both reduce the same
    values in the same blocks."""
    import repro
    g = generators.rmat(scale, 6, seed=seed)
    ranks = [np.asarray(repro.open(g, repro.EngineConfig(
        method=m, part_size=part_size, dangling="redistribute"))
        .pagerank().ranks) for m in ("pcpm", "pdpr")]
    np.testing.assert_array_equal(ranks[0], ranks[1])
