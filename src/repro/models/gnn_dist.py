"""PCPM-distributed GraphCast: message passing over the sharded PNG.

The baseline GNN forward (gnn.py) lets XLA implement ``h[edge_src]`` as
an ALL-GATHER of the full node tensor (N x C per device) and the
segment-sum as an ALL-REDUCE of full-size partials — the distributed
analogue of BVGAS (one value per cross-shard edge, plus full
materialization).  This module is the paper's technique applied instead:

  scatter phase   each shard sends h[u] ONCE per destination shard that
                  needs it (the deduplicated ``send_ids`` update list of
                  core/distributed.ShardedPNG) via one all-to-all of
                  dense compressed buffers;
  gather phase    each shard expands its receive buffer over its local
                  edge list (``edge_upd`` indices — the branch-free
                  analogue of the paper's MSB stream) and segment-sums
                  into LOCAL destinations only.

Per-device transient: S*U*C (receive buffer) instead of N*C
(all-gather); wire bytes divide by the wire compression r.  Used by the
dry-run ``--engine pcpm`` GNN cells and the §Perf hillclimb.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import GNNConfig
from ..core.distributed import ShardedPNG, build_sharded_png
from .gnn import mlp, init_graphcast


def _axis_names(mesh: Mesh):
    return tuple(mesh.axis_names)


@dataclasses.dataclass(frozen=True)
class DistGraph:
    """Per-shard static-shape graph structures (leading axis = shard)."""
    num_shards: int
    shard_size: int          # nodes per shard
    u_max: int               # updates per (src, dst) shard pair
    e_max: int               # edges per destination shard
    send_ids: jnp.ndarray    # (S, S, U) local src ids, pad -1
    edge_upd: jnp.ndarray    # (S, E) recv-buffer index, pad S*U
    edge_dst: jnp.ndarray    # (S, E) local dst ids, pad shard_size
    node_feat: jnp.ndarray   # (S*shard_size, d_feat)
    positions: jnp.ndarray   # (S*shard_size, 3)
    labels: jnp.ndarray      # (S*shard_size,)

    @staticmethod
    def from_png(layout: ShardedPNG, node_feat, positions, labels
                 ) -> "DistGraph":
        return DistGraph(
            layout.num_shards, layout.shard_size,
            int(layout.send_ids.shape[2]), int(layout.edge_upd.shape[1]),
            jnp.asarray(layout.send_ids), jnp.asarray(layout.edge_upd),
            jnp.asarray(layout.edge_dst), jnp.asarray(node_feat),
            jnp.asarray(positions), jnp.asarray(labels))

    @staticmethod
    def abstract(n_shards: int, shard_size: int, u_max: int, e_max: int,
                 d_feat: int) -> "DistGraph":
        """ShapeDtypeStruct stand-in for the dry run.  u_max/e_max are
        the padded layout sizes a production loader computes from the
        real graph (see EXPERIMENTS.md §Perf for the ogb estimate)."""
        sds = jax.ShapeDtypeStruct
        n = n_shards * shard_size
        return DistGraph(
            n_shards, shard_size, u_max, e_max,
            sds((n_shards, n_shards, u_max), jnp.int32),
            sds((n_shards, e_max), jnp.int32),
            sds((n_shards, e_max), jnp.int32),
            sds((n, d_feat), jnp.float32),
            sds((n, 3), jnp.float32),
            sds((n,), jnp.int32))


jax.tree_util.register_pytree_node(
    DistGraph,
    lambda d: ((d.send_ids, d.edge_upd, d.edge_dst, d.node_feat,
                d.positions, d.labels),
               (d.num_shards, d.shard_size, d.u_max, d.e_max)),
    lambda aux, ch: DistGraph(aux[0], aux[1], aux[2], aux[3], *ch))


def dist_graph_shardings(mesh: Mesh, like: DistGraph) -> DistGraph:
    """NamedSharding pytree matching DistGraph (vertex axis over ALL
    mesh axes; per-shard tables sharded on the leading shard dim).
    Pytree aux metadata is copied from ``like`` (jit requires the
    sharding prefix tree's metadata to match the argument's)."""
    ax = _axis_names(mesh)
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    return DistGraph(
        like.num_shards, like.shard_size, like.u_max, like.e_max,
        ns(ax, None, None), ns(ax, None), ns(ax, None),
        ns(ax, None), ns(ax, None), ns(ax))


def graphcast_dist_forward(params: dict, cfg: GNNConfig, g: DistGraph,
                           mesh: Mesh,
                           unroll_layers: bool = False) -> jnp.ndarray:
    """GraphCast forward with PCPM-exchange message passing.

    Same math as gnn.graphcast_forward for a graph whose edges are the
    sharded-PNG streams; returns (N, n_out) node outputs.  Layers scan
    (memory-bounded; see gnn._scan_gnn_layers) with per-layer remat;
    activations follow cfg.act_dtype.
    """
    ax = _axis_names(mesh)
    S, ssz, U = g.num_shards, g.shard_size, g.u_max
    d = cfg.d_hidden
    ad = jnp.dtype(cfg.act_dtype)

    def local(node_feat, positions, labels, send_ids, edge_upd,
              edge_dst, lparams):
        # shapes here are PER-DEVICE: node_feat (ssz, d_feat), tables
        # (1, ...) on their leading shard dim.
        send_ids, edge_upd, edge_dst = (send_ids[0], edge_upd[0],
                                        edge_dst[0])
        if ad != jnp.float32:
            cast = (lambda x: x.astype(ad)
                    if x.dtype == jnp.float32 else x)
            lparams = jax.tree.map(cast, lparams)
            node_feat, positions = cast(node_feat), cast(positions)
        h = mlp(lparams["node_enc"], node_feat)            # (ssz, d)

        def exchange(x):
            """PCPM scatter: dedup'd per-pair buffers, one all-to-all.
            x (ssz, c) -> recv (S*U + 1, c), last row = zero pad slot."""
            ids = send_ids                                  # (S, U)
            bufs = x[jnp.clip(ids, 0, ssz - 1)] \
                * (ids >= 0)[..., None].astype(x.dtype)     # (S, U, c)
            recv = jax.lax.all_to_all(bufs, ax, 0, 0, tiled=True)
            recv = recv.reshape(S * U, x.shape[-1])
            return jnp.concatenate(
                [recv, jnp.zeros((1, x.shape[-1]), x.dtype)], 0)

        # edge geometry from exchanged positions
        pos_recv = exchange(positions)                      # (S*U+1, 3)
        pos_src = pos_recv[edge_upd]                        # (E, 3)
        pos_dst = positions[jnp.clip(edge_dst, 0, ssz - 1)]
        rel = pos_src - pos_dst
        dist = jnp.sqrt(jnp.sum(rel * rel, -1, keepdims=True) + 1e-18)
        e0 = mlp(lparams["edge_enc"], jnp.concatenate([dist, rel], -1))
        valid = (edge_dst < ssz)[:, None].astype(e0.dtype)  # pad mask

        def layer(carry, lyr):
            h, e = carry
            hs = exchange(h)[edge_upd]                      # (E, d)
            hd = h[jnp.clip(edge_dst, 0, ssz - 1)]
            e = e + mlp(lyr["edge_mlp"],
                        jnp.concatenate([e, hs, hd], -1))
            agg = jax.ops.segment_sum(e * valid, edge_dst,
                                      num_segments=ssz + 1)[:ssz]
            h = h + mlp(lyr["node_mlp"], jnp.concatenate([h, agg], -1))
            return (h, e)

        from .gnn import _scan_gnn_layers
        h, _ = _scan_gnn_layers(layer, (h, e0), lparams["layers"],
                                unroll_layers)
        return mlp(lparams["dec"], h)                       # (ssz, n_out)

    vec = P(ax)
    mat1 = P(ax, None)
    mat2 = P(ax, None, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(mat1, mat1, vec, mat2, mat1, mat1, P()),
                       out_specs=mat1, check_vma=False)
    return fn(g.node_feat, g.positions, g.labels, g.send_ids,
              g.edge_upd, g.edge_dst, params)


def make_dist_train_step(cfg: GNNConfig, optimizer, mesh: Mesh, *,
                         n_out: int, unroll_layers: bool = False):
    def loss_fn(params, g: DistGraph):
        out = graphcast_dist_forward(params, cfg, g, mesh,
                                     unroll_layers)
        logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, g.labels[:, None], -1)[:, 0]
        return nll.mean()

    def step(params, opt_state, g: DistGraph):
        loss, grads = jax.value_and_grad(loss_fn)(params, g)
        params, opt_state, gnorm = optimizer.update(grads, opt_state,
                                                    params)
        return params, opt_state, {"loss": loss, "gnorm": gnorm}
    return step


# --------------------------------------------------- layout estimation
def estimate_u_max(n: int, e: int, s: int, *, skew: float = 4.0) -> int:
    """Padded updates per shard pair for a uniform-ish graph: unique
    sources u_p = Ns(1 - exp(-m_p/Ns)), padded by ``skew`` for degree
    skew, rounded to 128."""
    ns, mp = n / s, e / (s * s)
    u = ns * (1.0 - np.exp(-mp / ns)) * skew
    return max(128, int(-(-u // 128) * 128))
