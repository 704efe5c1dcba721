"""PCPM-distributed GraphCast == single-device baseline (subprocess
with 8 forced host devices, like test_distributed)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.configs import get
    from repro.graphs import generators
    from repro.core.distributed import build_sharded_png, pad_to_shards
    from repro.models.gnn import (GraphBatch, graphcast_forward,
                                  init_graphcast)
    from repro.models.gnn_dist import (DistGraph, graphcast_dist_forward,
                                       make_dist_train_step,
                                       dist_graph_shardings)
    from repro.optim import AdamW

    # Auto axes: the forward and the train step run under `with mesh`
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get("graphcast").scaled()
    g = generators.rmat(9, 8, seed=5)       # 512 nodes, 4096 edges
    n, m, df, n_out = g.num_nodes, g.num_edges, 12, 8
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((n, df)).astype(np.float32)
    pos = rng.standard_normal((n, 3)).astype(np.float32)
    pos /= np.linalg.norm(pos, axis=1, keepdims=True)
    labels = rng.integers(0, n_out, n).astype(np.int32)

    params = init_graphcast(cfg, jax.random.key(1), df, n_out)

    # baseline: plain edge-list forward
    gb = GraphBatch(jnp.asarray(g.src), jnp.asarray(g.dst),
                    jnp.ones(m, jnp.float32), jnp.asarray(feat),
                    jnp.asarray(pos), jnp.ones(n, jnp.float32),
                    jnp.zeros(n, jnp.int32), 1, jnp.asarray(labels))
    ref = np.asarray(graphcast_forward(params, cfg, gb))

    # PCPM-distributed forward over 8 shards
    layout = build_sharded_png(g, 8)
    dg = DistGraph.from_png(layout, pad_to_shards(feat, layout),
                            pad_to_shards(pos, layout),
                            pad_to_shards(labels, layout))
    with mesh:
        out = np.asarray(graphcast_dist_forward(params, cfg, dg, mesh))
    np.testing.assert_allclose(out[:n], ref, rtol=2e-4, atol=2e-5)
    print("dist forward matches baseline ok")

    # one train step runs and produces finite loss/grads
    opt = AdamW(lr=1e-3)
    step = jax.jit(make_dist_train_step(cfg, opt, mesh, n_out=n_out))
    with mesh:
        p2, s2, metrics = step(params, opt.init(params), dg)
    assert np.isfinite(float(metrics["loss"]))
    print("dist train step ok", float(metrics["loss"]))

    # the compiled program exchanges via all-to-all, not all-gather of
    # the full node tensor
    with mesh:
        txt = jax.jit(
            lambda p, d: graphcast_dist_forward(p, cfg, d, mesh)
        ).lower(params, dg).compile().as_text()
    assert "all-to-all" in txt
    print("uses all-to-all ok")
""")


def test_gnn_dist_pcpm():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    for marker in ["dist forward matches baseline ok",
                   "dist train step ok", "uses all-to-all ok"]:
        assert marker in proc.stdout
