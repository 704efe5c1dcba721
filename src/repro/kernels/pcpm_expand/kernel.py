"""The PCPM expand as a Pallas TPU kernel: ``bins[idx]`` read from a
VMEM-resident window of the destination partition's update bins.

The paper keeps each destination partition's bins in fast memory and
streams that partition's edges past them (alg. 5).  On a TPU the fast
memory is VMEM.  The dst-sorted arc stream is partition-major, so the
plan (``core.png.build_gather_schedule``) gives each block of arcs a
window of bin rows that holds every update it reads, and consecutive
blocks share a window until a block starts in the next partition.

- The bins stay in HBM, as ``(R, 128)`` rows.
- A ``(window_rows, 128)`` VMEM scratch holds the block's window.  The
  block's window start row is scalar-prefetched; the window is
  refilled by one DMA only where it differs from the previous block's.
- Each block's arcs come into SMEM as one word each, the update's
  window row and lane rotation (``_arc_words``, computed by XLA from
  the indices).  Per arc: one dynamic row load from the window, the
  update's lane rotated into the arc's lane, and a select into the
  output row.

The output is the ``(Mp,)`` f32 that ``bins[idx]`` gives, bit for bit.
The kernel runs compiled on the ``tpu`` platform and in the Pallas
interpreter on ``cpu`` (``pcpm_spmv.kernel.default_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..pcpm_spmv.kernel import default_interpret

LANES = 128
SUBLANES = 8
# scoped VMEM the kernel is compiled with (v5e's default scoped limit)
VMEM_LIMIT = 16 * 2 ** 20


def vmem_bytes(window_rows: int, block: int) -> int:
    """The kernel's VMEM working set: the window and the
    double-buffered output block."""
    return 4 * (window_rows * LANES + 2 * block)


def fits(window_rows: int, block: int) -> bool:
    return vmem_bytes(window_rows, block) <= VMEM_LIMIT


def _expand_kernel(start_ref, arc_ref, bins_ref, out_ref, window, sem, *,
                   window_rows: int):
    b = pl.program_id(0)
    start = start_ref[b]

    @pl.when((b == 0) | (start != start_ref[jnp.maximum(b - 1, 0)]))
    def _fill():
        copy = pltpu.make_async_copy(
            bins_ref.at[pl.ds(start, window_rows)], window, sem)
        copy.start()
        copy.wait()

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def row(r, carry):
        acc = jnp.zeros((1, LANES), jnp.float32)
        for j in range(LANES):
            a = arc_ref[r, j]
            v = window[pl.ds(a >> 7, 1), :]
            v = pltpu.roll(v, a & (LANES - 1), 1)
            acc = jnp.where(lane == j, v, acc)
        out_ref[pl.ds(r, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, arc_ref.shape[0], row, 0)


def _arc_words(idx, window_start, block_rows: int):
    """Per arc, its window row and the lane rotation that moves its
    update into the arc's own lane, as ``row << 7 | rotation``: the
    kernel's scalar work per arc is then two bit operations."""
    idx = idx.reshape(window_start.shape[0], block_rows, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 2)
    row = (idx >> 7) - window_start[:, None, None]
    return ((row << 7) | ((lane - idx) & (LANES - 1))).reshape(-1, LANES)


@functools.partial(jax.jit, static_argnames=("window_rows", "interpret"))
def window_expand(bins: jnp.ndarray, idx: jnp.ndarray,
                  window_start: jnp.ndarray, *, window_rows: int,
                  interpret: bool | None = None) -> jnp.ndarray:
    """``bins[idx]`` for ``bins`` (U,) f32 and ``idx`` (Mp,) int32,
    ``Mp`` a multiple of ``len(window_start)`` whole 128-lane rows.

    ``window_start[b]`` is the first bin row of block ``b``'s window:
    every index of the block lies in rows ``[window_start[b],
    window_start[b] + window_rows)``.  Windows are whole (8, 128) tiles
    (a DMA of part of a tile is never awaited in full) and lie within
    the bins' ``ceil(U / 128)`` rows rounded up to whole tiles."""
    interpret = default_interpret(interpret)
    (num_updates,) = bins.shape
    (mp,) = idx.shape
    nblocks = window_start.shape[0]
    block_rows = mp // (nblocks * LANES)
    assert block_rows * nblocks * LANES == mp, (mp, nblocks)
    rows = -(-max(-(-num_updates // LANES), 1) // SUBLANES) * SUBLANES
    assert window_rows % SUBLANES == 0 and window_rows <= rows, (
        window_rows, rows)
    bins = jnp.pad(bins, (0, rows * LANES - num_updates))
    kernel = functools.partial(_expand_kernel, window_rows=window_rows)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((block_rows, LANES), lambda b, s: (b, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((block_rows, LANES),
                                   lambda b, s: (b, 0)),
            scratch_shapes=[pltpu.VMEM((window_rows, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((mp // LANES, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(window_start, _arc_words(idx, window_start, block_rows),
      bins.reshape(rows, LANES))
    return out.reshape(mp)
