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
- Each arc comes in as two streams.  Its update's window row
  (``arc_rows``, made once per plan) comes into SMEM, so the scalar
  work per arc is one load and one address.  Its update's lane is the
  index's low 7 bits, read from the same block of indices in VMEM.
- Per 128 arcs (one output row): each arc's window row is copied into
  row ``j`` of a ``(128, 128)`` staging tile.  Then, on whole vregs,
  the tile's bits are transposed, so that ``t[l, j]`` is lane ``l`` of
  arc ``j``'s row; every ``t[l, j]`` but ``l == lane[j]`` is zeroed;
  and the 128 sublanes are folded into one by bitwise OR.  Exactly one
  word survives per arc and OR with zeros keeps its bits.

The output is the ``(Mp,)`` f32 that ``bins[idx]`` gives, bit for bit
(-0.0, NaN payloads and subnormals included).  The kernel runs compiled
on the ``tpu`` platform and in the Pallas interpreter on ``cpu``
(``pcpm_spmv.kernel.default_interpret``).
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
    """The kernel's VMEM working set: the window, the staging tile,
    and the double-buffered blocks of indices and output."""
    return 4 * (window_rows * LANES + LANES * LANES + 2 * 2 * block)


def fits(window_rows: int, block: int) -> bool:
    return vmem_bytes(window_rows, block) <= VMEM_LIMIT


def _expand_kernel(start_ref, row_ref, idx_ref, bins_ref, out_ref, window,
                   stage, sem, *, window_rows: int):
    b = pl.program_id(0)
    start = start_ref[b]

    @pl.when((b == 0) | (start != start_ref[jnp.maximum(b - 1, 0)]))
    def _fill():
        copy = pltpu.make_async_copy(
            bins_ref.at[pl.ds(start, window_rows)], window, sem)
        copy.start()
        copy.wait()

    sublane = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)

    def row(r, carry):
        for j in range(LANES):
            stage[pl.ds(j, 1), :] = window[pl.ds(row_ref[r, j], 1), :]
        t = pltpu.bitcast(stage[...], jnp.int32).T
        lane = idx_ref[pl.ds(r, 1), :] & (LANES - 1)
        t = jnp.where(sublane == lane, t, 0)
        acc = t[:SUBLANES]
        for k in range(SUBLANES, LANES, SUBLANES):
            acc = acc | t[k:k + SUBLANES]
        for shift in (4, 2, 1):
            acc = acc | pltpu.roll(acc, shift, 0)
        out_ref[pl.ds(r, 1), :] = pltpu.bitcast(acc[:1], jnp.float32)
        return carry

    jax.lax.fori_loop(0, row_ref.shape[0], row, 0)


@jax.jit
def arc_rows(idx: jnp.ndarray, window_start: jnp.ndarray) -> jnp.ndarray:
    """Per arc, the row of its block's window that holds its update,
    as ``(Mp / 128, 128)`` int32: the kernel's SMEM stream, made once
    per plan."""
    rows = (idx.reshape(window_start.shape[0], -1) >> 7
            ) - window_start[:, None]
    return rows.reshape(-1, LANES)


@functools.partial(jax.jit, static_argnames=("window_rows", "interpret"))
def window_expand(bins: jnp.ndarray, idx: jnp.ndarray,
                  window_start: jnp.ndarray, rows: jnp.ndarray, *,
                  window_rows: int,
                  interpret: bool | None = None) -> jnp.ndarray:
    """``bins[idx]`` for ``bins`` (U,) f32 and ``idx`` (Mp,) int32,
    ``Mp`` a multiple of ``len(window_start)`` whole 128-lane rows;
    ``rows`` is ``arc_rows(idx, window_start)``.

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
    assert rows.shape == (mp // LANES, LANES), (rows.shape, mp)
    bin_rows = -(-max(-(-num_updates // LANES), 1) // SUBLANES) * SUBLANES
    assert window_rows % SUBLANES == 0 and window_rows <= bin_rows, (
        window_rows, bin_rows)
    bins = jnp.pad(bins, (0, bin_rows * LANES - num_updates))
    kernel = functools.partial(_expand_kernel, window_rows=window_rows)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((block_rows, LANES), lambda b, s: (b, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((block_rows, LANES), lambda b, s: (b, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((block_rows, LANES),
                                   lambda b, s: (b, 0)),
            scratch_shapes=[pltpu.VMEM((window_rows, LANES), jnp.float32),
                            pltpu.VMEM((LANES, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((mp // LANES, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(window_start, rows, idx.reshape(-1, LANES),
      bins.reshape(bin_rows, LANES))
    return out.reshape(mp)
