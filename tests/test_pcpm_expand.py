"""The windowed expand kernel (kernels/pcpm_expand), interpreted on
the CPU at small sizes: it equals XLA's ``bins[idx]`` bit for bit."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import Partitioning, build_gather_schedule, build_png
from repro.graphs.formats import Graph
from repro.kernels.pcpm_expand import (arc_rows, fits, vmem_bytes,
                                       window_expand)

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


def _special_bins(rng, num_updates):
    """Bins whose bits a float sum or a max would not keep: -0.0,
    infinities, subnormals and NaNs with payloads, among others."""
    bins = rng.standard_normal(num_updates).astype(np.float32)
    bits = bins.view(np.int32)
    special = np.array([0x80000000, 0x7F800000, 0xFF800000, 0x00000001,
                        0x807FFFFF, 0x7FC01234, 0xFFBADBAD, 0x7F800001],
                       np.uint32).view(np.int32)
    bits[::3] = np.resize(special, bits[::3].shape)
    return bins


def _one_row_per_output_row(rng, num_updates, window_rows, starts):
    """Every arc of a 128-arc output row reads the same window row."""
    idx, win = _blocks(rng, num_updates, window_rows, starts)
    rows = idx.reshape(-1, 128)
    rows[:] = (rows[:, :1] // 128) * 128 + rng.integers(0, 128,
                                                        rows.shape)
    return idx, win


def _arc_j_reads_lane_j(rng, num_updates, window_rows, starts):
    """Arc ``j`` of each output row reads lane ``j`` of a row drawn
    from its window: the staging tile's diagonal."""
    idx, win = _blocks(rng, num_updates, window_rows, starts)
    rows = idx.reshape(-1, 128)
    rows[:] = (rows // 128) * 128 + np.arange(128)
    return idx, win


CASES = {
    # U, window rows, window start per block, pure-padding blocks;
    # windows are whole (8, 128) tiles
    "several_partitions": (3000, 8, [0, 0, 8, 16, 16], ()),
    # the last partition starts at row 16; its window moves down to 8
    "last_window_clamped": (2500, 16, [0, 8, 8], ()),
    "pure_padding_block": (2000, 8, [0, 8, 8, 8], (1, 3)),
    # every update in one 128-lane row
    "one_row_of_updates": (100, 8, [0, 0], ()),
    "special_values": (3000, 16, [0, 8, 8], ()),
    "one_window_row_per_output_row": (4096, 16, [0, 8, 16], ()),
    "arc_j_reads_lane_j": (4096, 16, [0, 8, 16], ()),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_xla_gather(case):
    num_updates, rows, starts, pad = CASES[case]
    rng = np.random.default_rng(len(case))
    make = {"one_window_row_per_output_row": _one_row_per_output_row,
            "arc_j_reads_lane_j": _arc_j_reads_lane_j}.get(case)
    if make is None:
        idx, win = _blocks(rng, num_updates, rows, starts, pad)
    else:
        idx, win = make(rng, num_updates, rows, starts)
    bins = (_special_bins(rng, num_updates) if case == "special_values"
            else rng.random(num_updates, dtype=np.float32))
    idx_d, win_d = jnp.asarray(idx), jnp.asarray(win)
    out = window_expand(jnp.asarray(bins), idx_d, win_d,
                        arc_rows(idx_d, win_d), window_rows=rows)
    np.testing.assert_array_equal(np.asarray(out).view(np.int32),
                                  bins[idx].view(np.int32))


def test_arc_rows_are_window_rows():
    """Each arc's row in its block's window, one row of 128 arcs per
    block here."""
    rng = np.random.default_rng(1)
    idx = np.concatenate([rng.integers(0, 1024, 128),
                          rng.integers(1024, 2048, 128)]).astype(np.int32)
    win = np.array([0, 8], np.int32)
    got = np.asarray(arc_rows(jnp.asarray(idx), jnp.asarray(win)))
    assert got.shape == (2, 128)
    np.testing.assert_array_equal(got.reshape(-1),
                                  idx // 128 - np.repeat(win, 128))


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
    eui = jnp.asarray(sched.edge_update_idx_padded)
    win = jnp.asarray(sched.window_start)
    out = window_expand(jnp.asarray(bins), eui, win, arc_rows(eui, win),
                        window_rows=sched.window_rows)
    np.testing.assert_array_equal(np.asarray(out),
                                  bins[sched.edge_update_idx_padded])


def test_vmem_budget():
    """The window, the (128, 128) staging tile, and the double-buffered
    blocks of indices and output."""
    assert vmem_bytes(1000, 8192) == 4 * (1000 * 128 + 128 * 128
                                          + 4 * 8192)
    assert fits(10464, 8192)                  # graph500-22's window
    assert fits(10240, 8192)
    assert fits(23680, 8192)                  # its relabeled hub window
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
