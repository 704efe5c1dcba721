"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler builds for a chip that is described
and not attached, and refuses what the chip's compiler would refuse
(block tiling, VMEM, HBM).  The topology is described inside a
module-scoped fixture, never at import, so that every test worker
collects the same tests and only the worker that runs this file loads
the TPU library.  A compile that passes is not a chip run.
"""
import functools

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.plan import DEFAULT_GATHER_BLOCK
from repro.core.spmv import pcpm_gather_blocked, pcpm_scatter
from repro.kernels.pcpm_spmv.kernel import (EDGE_BLOCK, EDGE_ROWS, LANES,
                                            max_part_size,
                                            pcpm_gather_pallas)

# graph500-22 (rmat(22, 16)) with the pcpm plan at part_size 65536:
# compression r = 3.20 gives U = m / r updates, and the gather schedule
# holds at most n + m / block pieces
N22 = 1 << 22
M22 = 16 * N22
U22 = int(M22 / 3.2)
PIECES22 = N22 + M22 // DEFAULT_GATHER_BLOCK
HBM_BYTES = 16 * 10 ** 9       # one v5e chip
# graph500-22 in LDBC Graphalytics' form, the benchmark's graph: its
# update count, arcs padded to whole expand-kernel blocks, and a window
# of 10240 rows (5.2 MB), two of its 37 partitions of 65536 vertices
NG22, UG22, MPG22, WG22 = 2_395_582, 24_004_616, 15_664 * 8192, 10_240
# the same graph relabeled by ``hybrid``: fewer updates, and one
# partition holding 90 % of the vertices, whose window is the widest
UH22, WH22 = 10_963_033, 23_680


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _gather_program(part_size: int, one_chip):
    k, num_updates = 2, 4 * 512
    n_eb = 2 * EDGE_ROWS                    # two grid steps of edge rows
    bins = jax.ShapeDtypeStruct((k, num_updates, LANES), jnp.float32,
                                sharding=one_chip)
    idx = jax.ShapeDtypeStruct((k, n_eb, EDGE_BLOCK), jnp.int32,
                               sharding=one_chip)
    fn = functools.partial(pcpm_gather_pallas, part_size=part_size,
                           interpret=False)
    return jax.jit(fn).lower(bins, idx, idx)


def test_pallas_gather_compiles_at_derived_part_size(one_chip):
    compiled = _gather_program(max_part_size(), one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_gather_refused_past_derived_part_size(one_chip):
    """The derivation is tight: twice the derived size overflows the
    scoped VMEM limit in the chip's compiler too."""
    with pytest.raises(Exception, match="vmem"):
        _gather_program(2 * max_part_size(), one_chip).compile()


def test_pcpm_spmv_compiles_at_graph500_22(one_chip):
    def spmv(upd, eui, ps, pe, pd, x):
        return pcpm_gather_blocked(pcpm_scatter(upd, x), eui, ps, pe, pd,
                                   num_nodes=N22,
                                   block=DEFAULT_GATHER_BLOCK)

    def i32(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

    x = jax.ShapeDtypeStruct((N22,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(spmv).lower(i32(U22), i32(M22), i32(PIECES22),
                                   i32(PIECES22), i32(PIECES22),
                                   x).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes >= 4 * (U22 + M22 + 3 * PIECES22)
    assert used < HBM_BYTES, used


def test_sharded_loop_compiles_for_four_chips(topo, monkeypatch):
    """The fused ``pcpm_sharded`` loop over a 2x2 mesh, its per-shard
    stream shapes those of ``rmat(14)`` grown 256x to graph500-22."""
    import dataclasses
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import distributed
    from repro.graphs.generators import rmat

    mesh = Mesh(np.array(topo.devices), ("shards",))
    small = distributed.build_sharded_png(rmat(14, 16, seed=0), 4)
    grow = N22 // small.num_nodes

    def grown(a):
        shape = a.shape[:-1] + (a.shape[-1] * grow,)
        return np.broadcast_to(np.int32(0), shape)

    layout = dataclasses.replace(
        small, shard_size=small.shard_size * grow, num_nodes=N22,
        send_ids=grown(small.send_ids), eui_padded=grown(small.eui_padded),
        piece_start=grown(small.piece_start),
        piece_end=grown(small.piece_end),
        piece_dst=grown(small.piece_dst))

    def spec(a):
        lead = P("shards", *([None] * (np.ndim(a) - 1)))
        return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                    sharding=NamedSharding(mesh, lead))

    # the described devices hold no arrays: the loop binds shapes instead
    monkeypatch.setattr(distributed, "_place",
                        lambda mesh, axis, *arrays: tuple(map(spec, arrays)))
    run = distributed.sharded_power_iteration(
        layout, mesh, "shards", num_iterations=20, dangling="redistribute")
    vec = spec(np.zeros(layout.padded_nodes, np.float32))
    compiled = run.func.lower(*run.args, vec, vec, vec).compile()
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    per_device = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert per_device < HBM_BYTES, per_device


# ---------------------------------------------------------------------------
# The pcpm expand's window kernel (kernels/pcpm_expand)
# ---------------------------------------------------------------------------
def test_windowed_expand_compiles_at_graph500_22(one_chip):
    from repro.kernels.pcpm_expand import fits, window_expand
    assert fits(WG22, 8192)
    fn = functools.partial(window_expand, window_rows=WG22,
                           interpret=False)
    args = (jax.ShapeDtypeStruct((UG22,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((MPG22,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((MPG22 // 8192,), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((MPG22 // LANES, LANES), jnp.int32,
                                 sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_windowed_expand_compiles_at_the_hub_window(one_chip):
    """The relabeled graph's window, about 2.3x the unrelabeled one's
    and 73 % of the kernel's VMEM budget, compiles within it."""
    from repro.kernels.pcpm_expand import fits, window_expand
    from repro.kernels.pcpm_expand.kernel import VMEM_LIMIT, vmem_bytes
    assert fits(WH22, 8192)
    assert vmem_bytes(WH22, 8192) > 0.7 * VMEM_LIMIT
    fn = functools.partial(window_expand, window_rows=WH22,
                           interpret=False)
    args = (jax.ShapeDtypeStruct((UH22,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((MPG22,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((MPG22 // 8192,), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((MPG22 // LANES, LANES), jnp.int32,
                                 sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fused_loop_text(spmv, num_nodes, one_chip):
    """The optimized HLO of the fused 20-pass pcpm loop over ``spmv``,
    compiled for one described v5e chip."""
    import types
    from repro.core.pagerank import fused_power_iteration

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    engine = types.SimpleNamespace(_fused_cache={},
                                   spmv_fn=lambda: spmv)
    loop = fused_power_iteration(engine, num_iterations=20,
                                 dangling="redistribute")
    vec = jax.ShapeDtypeStruct((num_nodes,), jnp.float32,
                               sharding=one_chip)
    spmv = jax.tree_util.tree_map(shape, loop.args[0])
    return loop.func.lower(spmv, vec, vec, vec).compile().as_text()


def _custom_calls(text):
    return [ln for ln in text.splitlines()
            if "tpu_custom_call" in ln and " custom-call(" in ln]


def test_fused_loop_holds_window_kernel_under_expand(one_chip, monkeypatch):
    """At the benchmark graph's shapes, the fused pcpm loop's expand
    is the window kernel, under the ``pcpm.expand`` scope."""
    import numpy as np
    from repro.core import backends
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def i32(*shape):
        return np.broadcast_to(np.int32(0), shape)

    pieces = NG22 + MPG22 // DEFAULT_GATHER_BLOCK
    spmv = jax.tree_util.Partial(
        functools.partial(backends._pcpm_pass, num_nodes=NG22,
                          block=DEFAULT_GATHER_BLOCK, window_rows=WG22),
        i32(UG22), i32(MPG22), i32(pieces), i32(pieces), i32(pieces),
        i32(MPG22 // 8192), i32(MPG22 // LANES, LANES))
    (call,) = _custom_calls(_fused_loop_text(spmv, NG22, one_chip))
    assert "/pcpm.expand/" in call


def test_window_past_budget_keeps_xla_gather(one_chip, monkeypatch):
    """A plan whose window fits compiles the kernel into the loop; the
    same plan with a window past the kernel's VMEM budget compiles
    XLA's gather in its place."""
    import dataclasses
    import repro
    from repro.core import backends
    from repro.core.plan import clear_plan_cache
    from repro.graphs import generators
    from repro.kernels.pcpm_expand.kernel import VMEM_LIMIT
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(backends, "_on_tpu", lambda: True)
    clear_plan_cache()
    g = generators.rmat(10, 8, seed=0)
    plan = repro.open(g, method="pcpm", part_size=256).plan
    past = VMEM_LIMIT // (4 * LANES)
    wide = dataclasses.replace(plan, schedule=dataclasses.replace(
        plan.schedule, window_rows=past), _device={})
    assert backends.window_engages(plan.schedule)
    assert not backends.window_engages(wide.schedule)
    for p, kernels in ((plan, 1), (wide, 0)):
        text = _fused_loop_text(backends.spmv_fn(p), g.num_nodes,
                                one_chip)
        assert len(_custom_calls(text)) == kernels
    clear_plan_cache()
