"""Per-kernel allclose vs pure-jnp oracle, interpret=True, shape sweeps."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.graphs import generators
from repro.core import Partitioning, build_png, block_png
from repro.kernels.pcpm_spmv import (kernel, pack_blocked,
                                     pcpm_spmv_pallas, pcpm_gather_pallas,
                                     pcpm_gather_ref)
from repro.kernels.embedding_bag import (embedding_bag,
                                         embedding_bag_pallas,
                                         embedding_bag_ref)
from repro.kernels.flash_attention import (attention, mha_ref,
                                           flash_attention_pallas)


RNG = np.random.default_rng(42)


# ------------------------------------------------------------- pcpm_spmv
class TestPCPMKernel:
    @pytest.mark.parametrize("scale,deg,part_size,d", [
        (6, 4, 16, 1), (7, 8, 32, 8), (8, 6, 64, 16), (7, 4, 128, 32),
    ])
    def test_spmv_matches_dense(self, scale, deg, part_size, d):
        g = generators.rmat(scale, deg, seed=scale)
        packed = pack_blocked(
            block_png(build_png(g, Partitioning(g.num_nodes, part_size))),
            g.num_nodes, edge_block=128)
        x = RNG.random((g.num_nodes, d)).astype(np.float32)
        y = np.asarray(pcpm_spmv_pallas(packed, jnp.asarray(x.squeeze()
                                        if d == 1 else x)))
        A = np.zeros((g.num_nodes, g.num_nodes))
        np.add.at(A, (g.src, g.dst), 1.0)
        ref = A.T @ x
        np.testing.assert_allclose(
            y.reshape(ref.shape[0], -1), ref.reshape(ref.shape[0], -1)
            if d > 1 else ref, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kernel_vs_ref_direct(self, dtype):
        # two grid steps of edge rows, as pack_blocked pads them
        k, U, d, P, Eb, neb = 4, 128, 128, 64, 128, 2 * kernel.EDGE_ROWS
        bins = jnp.asarray(RNG.random((k, U, d)), dtype=dtype)
        eu = jnp.asarray(RNG.integers(0, U + 1, (k, neb, Eb)), dtype=jnp.int32)
        ed = jnp.asarray(RNG.integers(0, P + 1, (k, neb, Eb)), dtype=jnp.int32)
        out_k = pcpm_gather_pallas(bins, eu, ed, part_size=P,
                                   interpret=True)
        out_r = pcpm_gather_ref(bins, eu, ed, part_size=P)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=tol, atol=tol)

    def test_empty_partition(self):
        # a partition with zero edges must produce zeros
        k, U, d, P, Eb = 2, 128, 128, 8, 128
        bins = jnp.asarray(RNG.random((k, U, d)).astype(np.float32))
        shape = (k, kernel.EDGE_ROWS, Eb)                # all padding
        eu = jnp.full(shape, U, dtype=jnp.int32)
        ed = jnp.full(shape, P, dtype=jnp.int32)
        out = pcpm_gather_pallas(bins, eu, ed, part_size=P, interpret=True)
        assert np.allclose(np.asarray(out), 0.0)

    @pytest.mark.parametrize("backend,interpret,want", [
        ("cpu", None, True), ("cpu", True, True),
        ("tpu", None, False), ("tpu", False, False),
        ("tpu", True, ValueError), ("gpu", None, RuntimeError),
    ])
    def test_interpreter_only_on_cpu(self, monkeypatch, backend, interpret,
                                     want):
        """A run that lost its chip fails; it never lands in the
        interpreter."""
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if isinstance(want, bool):
            assert kernel.default_interpret(interpret) is want
        else:
            with pytest.raises(want):
                kernel.default_interpret(interpret)

    def test_part_size_derived_from_vmem(self):
        import repro
        top = kernel.max_part_size()
        assert kernel.vmem_bytes(top) <= kernel.VMEM_LIMIT
        assert kernel.vmem_bytes(2 * top) > kernel.VMEM_LIMIT
        small = generators.rmat(6, 4, seed=0)       # fits one partition
        sess = repro.open(small, method="pcpm_pallas")
        assert sess.plan.part_size == small.num_nodes
        big = generators.rmat(top.bit_length(), 1, seed=0)
        assert repro.open(big, method="pcpm_pallas").plan.part_size == top
        with pytest.raises(ValueError, match="VMEM"):
            repro.open(big, method="pcpm_pallas", part_size=2 * top)


# ---------------------------------------------------------- embedding_bag
class TestEmbeddingBag:
    @pytest.mark.parametrize("v,d,b,l", [
        (512, 128, 8, 4), (1024, 64, 32, 16), (2048, 128, 64, 8),
    ])
    def test_pallas_vs_ref(self, v, d, b, l):
        table = jnp.asarray(RNG.random((v, d)).astype(np.float32))
        idx = jnp.asarray(RNG.integers(0, v, (b, l)), dtype=jnp.int32)
        w = jnp.asarray(RNG.random((b, l)).astype(np.float32))
        out = embedding_bag(table, idx, w, path="pallas")
        ref = embedding_bag_ref(table, idx, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_padding_indices_ignored(self):
        v, d = 512, 128
        table = jnp.asarray(RNG.random((v, d)).astype(np.float32))
        idx = jnp.asarray([[0, 1, v, v], [2, v, v, v]], dtype=jnp.int32)
        out = embedding_bag(table, idx, None, path="pallas")
        ref = np.asarray(table)[np.array([[0, 1], [2, 2]])]
        np.testing.assert_allclose(np.asarray(out)[0], ref[0].sum(0),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out)[1], np.asarray(table)[2],
                                   rtol=1e-5)

    def test_xla_path_matches(self):
        v, d, b, l = 1024, 64, 16, 8
        table = jnp.asarray(RNG.random((v, d)).astype(np.float32))
        idx = jnp.asarray(RNG.integers(0, v, (b, l)), dtype=jnp.int32)
        np.testing.assert_allclose(
            np.asarray(embedding_bag(table, idx, None, path="xla")),
            np.asarray(embedding_bag(table, idx, None, path="pallas")),
            rtol=1e-4, atol=1e-5)


# --------------------------------------------------------- flash attention
class TestFlashAttention:
    @pytest.mark.parametrize("b,hq,hkv,sq,d", [
        (1, 4, 4, 256, 64), (2, 8, 2, 128, 64), (1, 4, 1, 384, 128),
    ])
    def test_causal_vs_ref(self, b, hq, hkv, sq, d):
        q = jnp.asarray(RNG.standard_normal((b, sq, hq, d)),
                        dtype=jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, sq, hkv, d)),
                        dtype=jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, sq, hkv, d)),
                        dtype=jnp.float32)
        out = attention(q, k, v, causal=True, path="pallas")
        ref = attention(q, k, v, causal=True, path="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("window", [64, 128, 200])
    def test_sliding_window(self, window):
        b, h, s, d = 1, 2, 384, 64
        q = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype=jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype=jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype=jnp.float32)
        out = attention(q, k, v, causal=True, window=window, path="pallas")
        ref = attention(q, k, v, causal=True, window=window, path="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_unpadded_seq(self):
        """Sq not a multiple of the block size exercises kv_len masking."""
        b, h, s, d = 1, 2, 200, 64
        q = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype=jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype=jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype=jnp.float32)
        out = attention(q, k, v, causal=True, path="pallas")
        ref = attention(q, k, v, causal=True, path="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_bf16(self):
        b, h, s, d = 1, 2, 256, 64
        mk = lambda: jnp.asarray(RNG.standard_normal((b, s, h, d)),
                                 dtype=jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        out = attention(q, k, v, causal=True, path="pallas")
        ref = attention(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True, path="xla")
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=5e-2, atol=5e-2)
