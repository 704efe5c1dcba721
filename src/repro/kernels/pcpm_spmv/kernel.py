"""PCPM gather phase as a Pallas TPU kernel (v3: tile-aligned streams).

TPU-native adaptation of paper alg. 5 (see DESIGN.md §2):

- one destination partition's accumulator lives in VMEM for the whole
  pass (the paper's cache-resident partition);
- the update bin for that partition streams through VMEM one lane-sized
  ``u_tile`` slice at a time;
- the per-edge (update_idx, dst_local) streams are consumed
  ``EDGE_ROWS`` edge blocks per grid step, so their VMEM blocks are
  (8, Eb) tiles of the chip's (8, 128) layout;
- BOTH the update gather and the destination scatter are expressed as
  one-hot matmuls on the MXU — the branch-free replacement for the
  paper's MSB pointer trick (TPU vector lanes have no cheap data-
  dependent branch).

Grid: (num_partitions, num_edge_blocks / EDGE_ROWS, num_update_tiles);
update tiles iterate innermost, accumulating gathered values for the
current edge rows into a VMEM scratch, and the destination scatter
fires on the last tile.  The partition accumulator block is revisited
across the two inner grid axes (Pallas keeps it in VMEM across
consecutive grid steps with the same index_map output).

Shapes (all static, built by core.png.block_png + ops.pack_blocked):
  bins:        (k, U, d)   per-partition compressed update values
  edge_upd:    (k, E_blocks, Eb) int32, pad = U   (one-hot row -> 0)
  edge_dst:    (k, E_blocks, Eb) int32, pad = P   (one-hot row -> 0)
  out:         (k, P, d)   per-partition accumulated values

The kernel runs compiled on the ``tpu`` platform and in the Pallas
interpreter on ``cpu`` (the tests); any other platform is an error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128           # vector width the kernel is sized for
EDGE_ROWS = 8         # edge blocks per grid step: the tile's sublanes
EDGE_BLOCK = 512      # edges per block (Eb)
U_TILE = 512          # preferred update tile
P_TILE = 512          # preferred destination tile of the scatter
# scoped VMEM the kernel is compiled with (v5e's default scoped limit);
# ``vmem_bytes`` budgets the kernel's working set against it
VMEM_LIMIT = 16 * 2 ** 20
# the one-hot operand is exact in any precision; the values are not
_EXACT = jax.lax.Precision.HIGHEST


def default_interpret(interpret: bool | None = None) -> bool:
    """Interpreter on ``cpu``, compiled kernel on ``tpu``, error
    elsewhere — a run that lost its chip must not quietly fall back to
    the interpreter."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True if interpret is None else interpret
    if backend == "tpu":
        if interpret:
            raise ValueError("the Pallas interpreter runs only on the cpu "
                             "platform")
        return False
    raise RuntimeError(f"pcpm_pallas runs on tpu (compiled) or cpu "
                       f"(interpreted), not on {backend!r}")


def pick_u_tile(num_updates: int, *, preferred: int = U_TILE,
                lane: int = LANES) -> int:
    """Largest lane-multiple tile <= preferred that divides U."""
    for cand in range(min(preferred, num_updates), lane - 1, -lane):
        if num_updates % cand == 0:
            return cand
    return num_updates


def pick_p_tile(part_size: int) -> int:
    """Largest sublane-multiple tile <= P_TILE that divides P."""
    for cand in range(min(P_TILE, part_size), 7, -8):
        if part_size % cand == 0:
            return cand
    return part_size


def vmem_bytes(part_size: int) -> int:
    """The kernel's VMEM working set in bytes (4-byte words, LANES
    columns, the largest update tile): the double-buffered output
    block, the scatter one-hot tile and its iota, the gathered-values
    scratch, the gather one-hot and its iota, and the double-buffered
    index and bin tiles."""
    out_block = 2 * part_size * LANES
    scatter_onehot = 2 * pick_p_tile(part_size) * EDGE_BLOCK
    vals_scratch = EDGE_ROWS * LANES * EDGE_BLOCK
    gather_onehot = 2 * U_TILE * EDGE_BLOCK
    inputs = 2 * (2 * EDGE_ROWS * EDGE_BLOCK + LANES * U_TILE)
    return 4 * (out_block + scatter_onehot + vals_scratch + gather_onehot
                + inputs)


def max_part_size() -> int:
    """Largest power-of-two partition size whose working set fits the
    scoped VMEM limit."""
    part = LANES
    while vmem_bytes(2 * part) <= VMEM_LIMIT:
        part *= 2
    return part


def check_part_size(part_size: int) -> None:
    """Raise ``ValueError`` when ``part_size`` cannot fit the kernel's
    VMEM budget — at plan build, before the chip's compiler refuses."""
    need = vmem_bytes(part_size)
    if need > VMEM_LIMIT:
        raise ValueError(
            f"pcpm_pallas part_size={part_size} needs {need / 2**20:.1f} "
            f"MiB of VMEM, over the kernel's {VMEM_LIMIT / 2**20:.0f} MiB "
            f"limit; the largest that fits is {max_part_size()} (leave "
            "part_size unset to derive it)")


def _gather_kernel(edge_upd_ref, edge_dst_ref, bins_ref, out_ref,
                   vals_ref, *, part_size: int, p_tile: int, u_tile: int,
                   num_u_tiles: int):
    e = pl.program_id(1)
    u = pl.program_id(2)

    @pl.when((e == 0) & (u == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(u == 0)
    def _init_vals():
        vals_ref[...] = jnp.zeros_like(vals_ref)

    bins = bins_ref[0].astype(jnp.float32)                # (d, u_tile)
    eb = edge_upd_ref.shape[-1]
    # tiled gather-as-matmul: (d, u_tile) @ (u_tile, Eb) -> (d, Eb).
    # Pad indices (== U) match no tile and contribute zero columns.
    iota_u = (jax.lax.broadcasted_iota(jnp.int32, (u_tile, eb), 0)
              + u * u_tile)

    def gather_row(j, carry):
        upd_idx = edge_upd_ref[0, pl.ds(j, 1), :]         # (1, Eb)
        oh_upd = (iota_u == upd_idx).astype(jnp.float32)
        vals_ref[j] += jax.lax.dot(bins, oh_upd, precision=_EXACT,
                                   preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, EDGE_ROWS, gather_row, 0)

    @pl.when(u == num_u_tiles - 1)
    def _scatter():
        # scatter-as-matmul, P_TILE destination rows at a time:
        # (p_tile, Eb) . (d, Eb)^T -> (p_tile, d)
        iota_p = jax.lax.broadcasted_iota(jnp.int32, (p_tile, eb), 0)

        def scatter_row(j, carry):
            dst_idx = edge_dst_ref[0, pl.ds(j, 1), :]     # (1, Eb)
            vals = vals_ref[j]                            # (d, Eb)

            def scatter_tile(c, carry):
                base = pl.multiple_of(c * p_tile, p_tile)
                oh_dst = (iota_p + base == dst_idx).astype(jnp.float32)
                out_ref[0, pl.ds(base, p_tile), :] += jax.lax.dot_general(
                    oh_dst, vals, (((1,), (1,)), ((), ())),
                    precision=_EXACT,
                    preferred_element_type=jnp.float32).astype(out_ref.dtype)
                return carry

            return jax.lax.fori_loop(0, part_size // p_tile, scatter_tile,
                                     carry)

        jax.lax.fori_loop(0, EDGE_ROWS, scatter_row, 0)


@functools.partial(jax.jit,
                   static_argnames=("part_size", "u_tile", "interpret"))
def pcpm_gather_pallas(bins: jnp.ndarray, edge_upd: jnp.ndarray,
                       edge_dst: jnp.ndarray, *, part_size: int,
                       u_tile: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """bins: (k, U, d); edge_upd/edge_dst: (k, n_eb, Eb) -> (k, P, d).

    ``n_eb`` must be a multiple of ``EDGE_ROWS`` (``ops.pack_blocked``
    pads the streams so)."""
    interpret = default_interpret(interpret)
    k, num_updates, d = bins.shape
    _, n_eb, eb = edge_upd.shape
    assert edge_dst.shape == edge_upd.shape
    assert n_eb % EDGE_ROWS == 0, (n_eb, EDGE_ROWS)
    if u_tile is None:
        u_tile = pick_u_tile(num_updates)
    assert num_updates % u_tile == 0, (num_updates, u_tile)
    n_ut = num_updates // u_tile
    kernel = functools.partial(_gather_kernel, part_size=part_size,
                               p_tile=pick_p_tile(part_size),
                               u_tile=u_tile, num_u_tiles=n_ut)
    rows = pl.BlockSpec((1, EDGE_ROWS, eb), lambda p, e, u: (p, e, 0))
    return pl.pallas_call(
        kernel,
        grid=(k, n_eb // EDGE_ROWS, n_ut),
        in_specs=[
            rows, rows,
            pl.BlockSpec((1, d, u_tile), lambda p, e, u: (p, 0, u)),
        ],
        out_specs=pl.BlockSpec((1, part_size, d),
                               lambda p, e, u: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, part_size, d), bins.dtype),
        scratch_shapes=[pltpu.VMEM((EDGE_ROWS, d, eb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(edge_upd, edge_dst, jnp.swapaxes(bins, 1, 2))
