"""Jit'd wrapper: BlockedPNG + feature matrix -> full PCPM SpMV using the
Pallas gather kernel (scatter phase is an XLA gather producing the bins).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core.png import BlockedPNG
from .kernel import EDGE_BLOCK, EDGE_ROWS, LANES, pcpm_gather_pallas


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@dataclasses.dataclass(frozen=True)
class PackedPNG:
    """Kernel-ready PNG blocks (device arrays, TPU-aligned padding)."""
    part_size: int
    num_nodes: int
    update_src: jnp.ndarray    # (k, U) int32, pad -> 0 (masked)
    update_valid: jnp.ndarray  # (k, U) bool
    edge_upd: jnp.ndarray      # (k, n_eb, Eb) int32, pad -> U
    edge_dst: jnp.ndarray      # (k, n_eb, Eb) int32, pad -> part_size

    @property
    def num_partitions(self) -> int:
        return self.update_src.shape[0]


def pack_blocked(blocked: BlockedPNG, num_nodes: int, *,
                 edge_block: int = EDGE_BLOCK,
                 lane: int = LANES) -> PackedPNG:
    """Pad the blocked PNG to the kernel's tiles: U to a lane multiple,
    the edge streams to whole (EDGE_ROWS, edge_block) tiles."""
    k, max_u = blocked.update_src.shape
    _, max_e = blocked.edge_update_local.shape
    u_pad = _round_up(max(max_u, lane), lane)
    e_pad = _round_up(max(max_e, 1), EDGE_ROWS * edge_block)

    upd = np.zeros((k, u_pad), dtype=np.int32)
    valid = np.zeros((k, u_pad), dtype=bool)
    upd[:, :max_u] = np.maximum(blocked.update_src, 0)
    valid[:, :max_u] = blocked.update_src >= 0

    eu = np.full((k, e_pad), u_pad, dtype=np.int32)
    ed = np.full((k, e_pad), blocked.part_size, dtype=np.int32)
    eu[:, :max_e] = np.where(blocked.edge_update_local >= max_u, u_pad,
                             blocked.edge_update_local)
    ed[:, :max_e] = blocked.edge_dst_local

    n_eb = e_pad // edge_block
    return PackedPNG(
        blocked.part_size, num_nodes,
        jnp.asarray(upd), jnp.asarray(valid),
        jnp.asarray(eu.reshape(k, n_eb, edge_block)),
        jnp.asarray(ed.reshape(k, n_eb, edge_block)))


@functools.partial(jax.jit, static_argnames=("interpret", "u_tile"))
def pcpm_spmv_pallas(packed: PackedPNG, x: jnp.ndarray, *,
                     interpret: bool | None = None,
                     u_tile: int | None = None) -> jnp.ndarray:
    """y = A^T x. x: (n,) or (n, d) with any d >= 1 (multi-vector /
    personalized-query batches).  Columns go through the kernel
    ``LANES`` at a time, padded to the lane width, so its VMEM working
    set never depends on d.

    ``interpret=None`` compiles the kernel on TPU and interprets it on
    the CPU (kernel.default_interpret)."""
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    n, d = x.shape
    d_pad = _round_up(d, LANES)
    if d_pad != d:
        x = jnp.pad(x, ((0, 0), (0, d_pad - d)))
    # scatter phase: compressed bins (k, U, d) — one value per
    # (src, dst-partition) pair, the paper's update_bins.
    bins = x[packed.update_src] * packed.update_valid[..., None]
    out = jnp.concatenate([
        pcpm_gather_pallas(bins[..., c:c + LANES], packed.edge_upd,
                           packed.edge_dst, part_size=packed.part_size,
                           interpret=interpret, u_tile=u_tile)
        for c in range(0, d_pad, LANES)], axis=-1)
    y = out.reshape(-1, d_pad)[:n, :d]
    return y[:, 0] if squeeze else y


# jax.jit can't take the dataclass directly unless registered as pytree:
jax.tree_util.register_pytree_node(
    PackedPNG,
    lambda p: ((p.update_src, p.update_valid, p.edge_upd, p.edge_dst),
               (p.part_size, p.num_nodes)),
    lambda aux, ch: PackedPNG(aux[0], aux[1], *ch))
