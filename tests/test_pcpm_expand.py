"""The windowed expand kernel (kernels/pcpm_expand), interpreted on
the CPU at small sizes: it equals XLA's ``bins[idx]`` bit for bit."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import Partitioning, build_gather_schedule, build_png
from repro.graphs.formats import Graph
from repro.kernels.pcpm_expand import fits, vmem_bytes, window_expand

KB = 1024                     # arcs per kernel block: one (8, 128) tile


def _blocks(rng, num_updates, window_rows, starts, pad=()):
    """One kernel block per window start: indices drawn from the
    window (clipped to the bins), the blocks in ``pad`` all padding."""
    idx = []
    for b, s in enumerate(starts):
        lo = s * 128
        hi = min((s + window_rows) * 128, num_updates)
        blk = (np.full(KB, lo) if b in pad
               else rng.integers(lo, hi, KB))
        idx.append(blk)
    return (np.concatenate(idx).astype(np.int32),
            np.asarray(starts, np.int32))


CASES = {
    # U, window rows, window start per block, pure-padding blocks;
    # windows are whole (8, 128) tiles
    "several_partitions": (3000, 8, [0, 0, 8, 16, 16], ()),
    # the last partition starts at row 16; its window moves down to 8
    "last_window_clamped": (2500, 16, [0, 8, 8], ()),
    "pure_padding_block": (2000, 8, [0, 8, 8, 8], (1, 3)),
    # every update in one 128-lane row
    "one_row_of_updates": (100, 8, [0, 0], ()),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_xla_gather(case):
    num_updates, rows, starts, pad = CASES[case]
    rng = np.random.default_rng(len(case))
    idx, win = _blocks(rng, num_updates, rows, starts, pad)
    bins = rng.random(num_updates, dtype=np.float32)
    out = window_expand(jnp.asarray(bins), jnp.asarray(idx),
                        jnp.asarray(win), window_rows=rows)
    np.testing.assert_array_equal(np.asarray(out), bins[idx])


def test_kernel_on_a_schedule_with_an_empty_partition():
    """A plan's own schedule, whose second partition has no arcs."""
    rng = np.random.default_rng(3)
    n, psz = 4096, 1024
    src = rng.integers(0, n, 30000).astype(np.int32)
    dst = rng.integers(0, n, 30000).astype(np.int32)
    dst[(dst >= psz) & (dst < 2 * psz)] += psz        # partition 1 empty
    layout = build_png(Graph(n, src, dst), Partitioning(n, psz))
    assert np.diff(layout.edge_offsets)[1] == 0
    sched = build_gather_schedule(layout)
    assert sched.kernel_block == 8192
    bins = rng.random(layout.num_updates, dtype=np.float32)
    eui = sched.edge_update_idx_padded
    out = window_expand(jnp.asarray(bins), jnp.asarray(eui),
                        jnp.asarray(sched.window_start),
                        window_rows=sched.window_rows)
    np.testing.assert_array_equal(np.asarray(out), bins[eui])


def test_vmem_budget():
    assert vmem_bytes(1000, 8192) == 4 * (1000 * 128 + 2 * 8192)
    assert fits(10240, 8192)                  # graph500-22's window
    assert not fits(33000, 8192)              # 16.9 MB of window


# ---------------------------------------------------------------------------
# When the kernel engages, and its counters
# ---------------------------------------------------------------------------
COUNTERS = ("pcpm_expand_window_share", "pcpm_expand_window_loads",
            "pcpm_expand_pad_share")


def _observed_solve(g, part_size):
    import repro
    from repro.core.plan import clear_plan_cache
    clear_plan_cache()
    sess = repro.open(g, repro.EngineConfig(method="pcpm",
                                            part_size=part_size,
                                            observe=True))
    res = sess.pagerank()
    reg = sess.obs.registry
    gauges = {k: reg.family_items(k)[0][1].value for k in COUNTERS}
    stats = sess.stats()["expand"]
    sess.obs.close()
    return sess, res, gauges, stats


def test_counters_on_the_kernel_path(monkeypatch):
    """The kernel path forced (interpreted here): every arc comes from
    a resident window, one fill per distinct window, and the ranks are
    XLA's."""
    from repro.core import backends
    from repro.graphs import generators
    g = generators.rmat(13, 8, seed=4)          # 8 kernel blocks
    _, ref, off, off_stats = _observed_solve(g, 2048)
    assert off == off_stats
    assert off["pcpm_expand_window_share"] == 0.0
    assert off["pcpm_expand_window_loads"] == 0
    monkeypatch.setattr(backends, "_on_tpu", lambda: True)
    sess, res, on, on_stats = _observed_solve(g, 2048)
    assert on == on_stats
    win = sess.plan.schedule.window_start
    assert on["pcpm_expand_window_share"] == 1.0
    assert on["pcpm_expand_window_loads"] == len(
        [i for i in range(len(win)) if i == 0 or win[i] != win[i - 1]])
    # at most one per partition: windows the clamp moves down can meet
    assert 1 < on["pcpm_expand_window_loads"] <= 4
    assert on["pcpm_expand_pad_share"] < 8192 / g.num_edges
    assert on["pcpm_expand_pad_share"] == off["pcpm_expand_pad_share"]
    np.testing.assert_array_equal(np.asarray(res.ranks),
                                  np.asarray(ref.ranks))


def test_xla_gather_keeps_other_shapes(monkeypatch):
    """With the kernel path open, a multi-column pass, a schedule
    without windows and a window past the VMEM budget keep XLA's
    gather; off a TPU nothing engages."""
    import jax
    import repro
    from repro.core import backends
    from repro.core.png import GatherSchedule
    from repro.core.plan import clear_plan_cache
    from repro.graphs import generators
    monkeypatch.setattr(backends, "_on_tpu", lambda: True)
    clear_plan_cache()
    g = generators.rmat(8, 8, seed=2)
    spmv = backends.spmv_fn(repro.open(g, method="pcpm", part_size=64).plan)

    def kernels(shape):
        jaxpr = jax.make_jaxpr(spmv)(jnp.zeros(shape, jnp.float32))
        return str(jaxpr).count("pallas_call")

    assert kernels((g.num_nodes,)) == 1
    assert kernels((g.num_nodes, 3)) == 0
    win = np.zeros(1, np.int32)
    sched = GatherSchedule(256, 10, np.zeros(1024, np.int32), win, win,
                           win, win, 4)
    assert backends.window_engages(sched)
    assert not backends.window_engages(
        GatherSchedule(256, 10, np.zeros(1024, np.int32), win, win, win))
    assert not backends.window_engages(
        GatherSchedule(256, 10, np.zeros(1024, np.int32), win, win, win,
                       win, 40000))
    monkeypatch.setattr(backends, "_on_tpu", lambda: False)
    assert not backends.window_engages(sched)
